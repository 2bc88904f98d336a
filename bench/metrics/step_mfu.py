"""The whole step's share of the chip's peak, in %: model FLOPs of the
requests answered in the traced window (unpadded shapes, ``flops`` of
the configuration's model file ``models/<kind>.py``) over window
seconds x peak bf16 FLOP/s x chips."""


def read(run):
    if run.trace is None or not run.answered or not run.peak_flops:
        return None
    return 100.0 * run.flops_answered / (
        run.trace.window_s * run.peak_flops * run.chips)
