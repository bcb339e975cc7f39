"""Analysis-layer tests: HLO collective parsing on synthetic HLO text and
roofline term arithmetic on a real compiled program."""
import jax
import jax.numpy as jnp
import pytest

from repro.analysis import collective_stats, estimate_model_flops
from repro.analysis.roofline import PEAKS, analyze
from repro.configs import get_config, get_shape

_FAKE_HLO = """
HloModule jit_step
  %x = f32[128,256]{1,0} parameter(0)
  %ar = f32[128,256]{1,0} all-reduce(f32[128,256]{1,0} %x), replica_groups={}
  %ag = bf16[64,512]{1,0} all-gather(bf16[4,512]{1,0} %y), dimensions={0}
  %rs = f32[32,16]{1,0} reduce-scatter(f32[512,16]{1,0} %z), dimensions={0}
  %aa = s32[1024]{0} all-to-all(s32[1024]{0} %w)
  %cp = f32[8]{0} collective-permute(f32[8]{0} %v)
  %dot = f32[128,256]{1,0} dot(f32[128,256]{1,0} %x, f32[256,256]{1,0} %m)
"""


def test_collective_stats_parses_all_types():
    st = collective_stats(_FAKE_HLO)
    by = st["by_op"]
    assert by["all-reduce"]["count"] == 1
    assert by["all-reduce"]["operand_bytes"] == 128 * 256 * 4
    assert by["all-reduce"]["wire_bytes"] == 2 * 128 * 256 * 4
    # all-gather wire uses the RESULT size (gathered tensor)
    assert by["all-gather"]["wire_bytes"] == 64 * 512 * 2
    assert by["reduce-scatter"]["operand_bytes"] == 512 * 16 * 4
    assert by["all-to-all"]["count"] == 1
    assert by["collective-permute"]["count"] == 1
    assert st["total"]["count"] == 5
    # the dot is not a collective
    assert st["total"]["operand_bytes"] < 10 * 128 * 256 * 4


def test_roofline_on_real_compiled_program():
    @jax.jit
    def f(a, b):
        return a @ b

    a = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    compiled = f.lower(a, a).compile()
    rep = analyze(compiled, arch="toy", shape="matmul", mesh_name="1",
                  n_devices=1, model_flops=2 * 256**3,
                  device_kind="TPU v5 lite")
    assert rep.flops_per_dev > 0
    assert rep.compute_s == (rep.flops_per_dev
                             / PEAKS["TPU v5 lite"]["peak_flops"])
    assert rep.dominant in ("compute", "memory", "collective")
    assert 0 < rep.useful_ratio <= 1.5


def test_roofline_unknown_device_kind_raises():
    """Peaks come only from the published table: a device that is not
    in it (here the host CPU) is an error, not a v5e stand-in."""
    compiled = jax.jit(lambda a: a + 1).lower(
        jax.ShapeDtypeStruct((8,), jnp.float32)).compile()
    with pytest.raises(ValueError, match="no published peaks"):
        analyze(compiled, arch="toy", shape="add", mesh_name="1",
                n_devices=1, model_flops=8,
                device_kind=jax.devices()[0].device_kind)


def test_model_flops_estimates_sane():
    kimi = get_config("kimi-k2-1t-a32b")
    tr = estimate_model_flops("lm", kimi, get_shape("kimi-k2-1t-a32b",
                                                    "train_4k"))
    # 6 * 32.1e9 active * 1.05e6 tokens ~ 2.0e17
    assert 1e17 < tr < 4e17
    dec = estimate_model_flops("lm", kimi, get_shape("kimi-k2-1t-a32b",
                                                     "decode_32k"))
    assert dec < tr / 1000
    dl = estimate_model_flops(
        "recsys", get_config("dlrm-mlperf"),
        get_shape("dlrm-mlperf", "train_batch"))
    assert dl > 1e11
