"""Roofline terms from `Compiled.cost_analysis()`, priced on the peaks
of a named device kind (a kind without published peaks is an error)."""
import pytest

from repro.analysis.roofline import V5E, analyze_compiled, peaks_for


class FakeCompiled:
    """Just enough Compiled surface for analyze_compiled."""

    def __init__(self, ca):
        self._ca = ca

    def cost_analysis(self):
        return self._ca

    def memory_analysis(self):
        raise RuntimeError("no memory analysis in this fake")

    def as_text(self):
        return ""


CA_DICT = {"flops": 1024.0, "bytes accessed": 768.0, "utilization0{}": 1.0}


def test_analyze_compiled_from_cost_analysis():
    roof = analyze_compiled("arch", "cell", "16x16", 256,
                            FakeCompiled(CA_DICT), model_flops=512.0,
                            device_kind=V5E)
    assert roof.hlo_flops == 1024.0
    assert roof.hlo_bytes == 768.0
    assert roof.collective_bytes == 0.0
    assert roof.per_device_memory == 0.0  # memory_analysis raised -> 0
    assert roof.bottleneck in ("compute", "memory", "collective")


def test_analyze_compiled_real_jit():
    """The shape actually returned by this environment's JAX must work."""
    import jax
    import jax.numpy as jnp
    compiled = jax.jit(lambda x: x @ x).lower(jnp.ones((8, 8))).compile()
    roof = analyze_compiled("arch", "cell", "1x1", 1, compiled,
                            model_flops=2 * 8 * 8 * 8, device_kind=V5E)
    assert roof.hlo_flops > 0


def test_v5e_peaks_are_the_published_ones():
    p = peaks_for(V5E)
    assert (p.flops, p.hbm_bw) == (197e12, 819e9)


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no published peaks"):
        peaks_for("cpu")
    with pytest.raises(KeyError):
        analyze_compiled("arch", "cell", "1x1", 1, FakeCompiled(CA_DICT),
                         model_flops=1.0, device_kind="cpu")
