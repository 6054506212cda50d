"""Roofline HLO parsing: synthetic HLO text + a real compiled module."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.roofline import analysis as RL
from repro.roofline import peaks as PK


SYNTH = """
  %ag = bf16[1024,512]{1,0} all-gather(%x), replica_groups={{0,1,2,3}}, dimensions={0}
  %ar = f32[2048]{0} all-reduce(%y), replica_groups=[4,2]<=[2,4]T(1,0), to_apply=%add
  %rs = f32[512]{0} reduce-scatter(%z), replica_groups={{0,1,2,3},{4,5,6,7}}, dimensions={0}
  %cp = bf16[64,64]{1,0} collective-permute(%w), source_target_pairs={{0,1}}
  %a2a = s8[4096]{0} all-to-all(%v), replica_groups={{0,1,2,3}}
"""


def test_parse_collectives_ring_model():
    ops = RL.parse_collectives(SYNTH, n_devices=8, devices_per_pod=4)
    by = {o.kind: o for o in ops}
    # all-gather bf16[1024,512]: R = 1MiB, g=4 -> (3/4) R
    assert by["all-gather"].result_bytes == 1024 * 512 * 2
    assert by["all-gather"].bytes_per_device == pytest.approx(
        1024 * 512 * 2 * 3 / 4)
    # all-reduce groups [4,2]<=[2,4]T(1,0): group size 2, crosses pods
    assert by["all-reduce"].group_size == 2
    assert by["all-reduce"].crosses_pod
    assert by["all-reduce"].bytes_per_device == pytest.approx(
        2 * 2048 * 4 * 1 / 2)
    # reduce-scatter result is the shard: (g-1) * R
    assert by["reduce-scatter"].bytes_per_device == pytest.approx(
        3 * 512 * 4)
    assert not by["reduce-scatter"].crosses_pod
    assert by["collective-permute"].bytes_per_device == 64 * 64 * 2
    assert by["all-to-all"].bytes_per_device == pytest.approx(4096 * 3 / 4)


def test_iota_group_parsing():
    g = RL._parse_groups("replica_groups=[4,2]<=[2,4]T(1,0)")
    assert g.shape == (4, 2)
    # iota [2,4] transposed (1,0) -> [4,2]: groups pair across the leading dim
    np.testing.assert_array_equal(g[0], [0, 4])


def test_shape_bytes_tuple():
    assert RL._shape_bytes("(f32[10], bf16[4,4])") == 40 + 32
    assert RL._shape_bytes("f8e4m3fn[100]") == 100
    assert RL._shape_bytes("pred[7]") == 7


def test_analyze_real_compiled():
    """cost_analysis + collective parse on an actually compiled module."""
    def f(x, w):
        return jnp.dot(x, w)

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    compiled = jax.jit(f).lower(x, w).compile()
    import types
    arch = types.SimpleNamespace(active_param_count=lambda: 0)
    rep = RL.analyze(compiled, arch="t", shape="s", mesh_desc="1",
                     n_devices=1, model_flops=2 * 256**3)
    assert rep.flops_per_device >= 2 * 256**3 * 0.9
    assert rep.bytes_per_device > 0
    assert rep.collective_s == 0.0
    assert rep.bottleneck in ("compute", "memory")
    s = rep.summary()
    assert set(s) >= {"bottleneck", "step_time_s", "roofline_fraction"}


def test_report_terms_math():
    rep = RL.RooflineReport(
        arch="a", shape="s", mesh="m", n_devices=2,
        flops_per_device=RL.PEAK_FLOPS,      # exactly 1s of compute
        bytes_per_device=RL.HBM_BW / 2,      # 0.5s memory
        ici_bytes_per_device=RL.ICI_BW / 4,  # 0.25s
        dcn_bytes_per_device=0.0,
        collectives=[], model_flops=RL.PEAK_FLOPS,
        memory_per_device={})
    assert rep.compute_s == pytest.approx(1.0)
    assert rep.memory_s == pytest.approx(0.5)
    assert rep.collective_s == pytest.approx(0.25)
    assert rep.bottleneck == "compute"
    assert rep.step_time_s == pytest.approx(1.0)
    assert rep.roofline_fraction == pytest.approx(1.0 / 1.75)
    assert rep.useful_flops_fraction == pytest.approx(0.5)


def test_chip_peaks_keyed_by_device_kind():
    """One peak table keyed by device_kind: a known TPU gets its row, an
    unknown TPU kind is an error, and off a TPU the model target is used
    by name -- the analytic constants are that same row."""
    class Dev:
        def __init__(self, platform, kind):
            self.platform, self.device_kind = platform, kind

    v5e = PK.chip_peaks(Dev("tpu", "TPU v5 lite"))
    assert (v5e.bf16_flops, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9,
                                                           16e9)
    with pytest.raises(KeyError, match="TPU v9"):
        PK.chip_peaks(Dev("tpu", "TPU v9"))
    assert PK.chip_peaks(Dev("cpu", "cpu")) is PK.MODEL_TARGET
    from repro.assist import tasks
    for mod in (RL, tasks):
        assert (mod.PEAK_FLOPS, mod.HBM_BW, mod.ICI_BW) == (
            PK.MODEL_TARGET.bf16_flops, PK.MODEL_TARGET.hbm_bw,
            PK.MODEL_TARGET.ici_link_bw)
