import os
import sys

CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (os.path.join(CHECKOUT, "src"), CHECKOUT):
    if p not in sys.path:
        sys.path.insert(0, p)
