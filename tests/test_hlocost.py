"""While-aware HLO cost model: exact trip attribution (the raw
cost_analysis counts scan bodies once -- demonstrated here)."""
import jax
import jax.numpy as jnp
import pytest

from repro.roofline import hlocost


def _compile(f, *specs):
    return jax.jit(f).lower(*specs).compile()


def test_cost_analysis_undercounts_scans():
    """The motivating defect: XLA counts while bodies once."""
    def body(c, _):
        return jnp.dot(c, c), None

    def f(x):
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    c = _compile(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    raw = c.cost_analysis()["flops"]
    assert raw == pytest.approx(2 * 128**3, rel=0.01)      # ONE body only


def test_hlocost_scan_exact():
    def body(c, _):
        return jnp.dot(c, c), None

    def f(x):
        y, _ = jax.lax.scan(body, x, None, length=10)
        return y

    c = _compile(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    cost = hlocost.analyze_text(c.as_text(), n_devices=1)
    assert cost.flops == pytest.approx(10 * 2 * 128**3, rel=0.01)
    assert cost.unparsed_trip_whiles == 0


def test_hlocost_nested_scans():
    def inner(c, _):
        return jnp.dot(c, c), None

    def outer(c, _):
        c, _ = jax.lax.scan(inner, c, None, length=5)
        return c, None

    def f(x):
        y, _ = jax.lax.scan(outer, x, None, length=4)
        return y

    c = _compile(f, jax.ShapeDtypeStruct((128, 128), jnp.float32))
    cost = hlocost.analyze_text(c.as_text(), n_devices=1)
    assert cost.flops == pytest.approx(4 * 5 * 2 * 128**3, rel=0.01)


def test_hlocost_scan_matches_unscanned_model():
    """Scanned stack == same stack as one unrolled pattern (both via
    hlocost), and within 15% of cost_analysis on the unrolled form."""
    import dataclasses
    from repro.configs import ARCHS, reduced
    from repro.configs.base import ShapeConfig
    from repro.models.model import build_model, input_specs

    cfg = dataclasses.replace(reduced(ARCHS["qwen2-7b"]), n_layers=6)
    cfg_flat = dataclasses.replace(cfg, block_pattern=("attn",) * 6)
    shape = ShapeConfig("s", 128, 2, "train")
    specs = input_specs(cfg, shape)

    def grad_of(c):
        m = build_model(c, remat=False)
        p = jax.eval_shape(lambda: m.init(jax.random.PRNGKey(0)))
        return _compile(jax.grad(lambda pp, b: m.loss(pp, b)[0]), p, specs)

    scan_c = grad_of(cfg)
    flat_c = grad_of(cfg_flat)
    got_scan = hlocost.analyze_text(scan_c.as_text(), n_devices=1)
    got_flat = hlocost.analyze_text(flat_c.as_text(), n_devices=1)
    assert got_scan.flops == pytest.approx(got_flat.flops, rel=0.02)
    truth = flat_c.cost_analysis()["flops"]
    assert got_flat.flops == pytest.approx(truth, rel=0.15)  # dots dominate
