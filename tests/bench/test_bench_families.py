"""Family modules: each configuration names the file that holds its
model code (``bench/families/<family>.py``), and the harness takes the
architecture, the weights, the float32 reference and the counts from it
alone.

``dense_gqa`` is an adapter over ``bench.weights``, ``bench.reference``
and ``bench.flops``: its draw and its counts are theirs, bit for bit. A
second family that exists only as a file of a test's checkout runs a
cell to ``correct`` with no other file of ``bench/`` changed."""
from __future__ import annotations

import inspect
import json
import time

import jax
import numpy as np
import pytest

from bench import families, harness
from bench import flops as F
from bench import weights as W
from bench.peaks import PEAKS
from bench_fixtures import ROOT, TINY_CONFIG, TINY_LIMITS, make_tiny_root

CONFIGS = sorted(p.stem for p in (ROOT / "bench" / "configs").glob("*.json"))
INTERFACE = {"arch", "make_params", "gaps", "counts"}


def _conf(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json")
                      .read_text())


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_names_its_family(name):
    conf = _conf(name)
    fam = families.load(conf)
    defined = {k for k, v in vars(fam).items()
               if inspect.isfunction(v) and v.__module__ == fam.__name__}
    assert defined == INTERFACE
    assert all(getattr(fam, k).__doc__ for k in INTERFACE)
    cfg = fam.arch(conf)
    c = conf["config"]
    assert (cfg.n_layers, cfg.d_model, cfg.vocab_size) == (
        c["num_hidden_layers"], c["hidden_size"], c["vocab_size"])


#: lanes' context lengths of one decode step: one lane, a page boundary,
#: a full chat batch, long lanes beside a short one
CTXS = [[1], [16, 17], [600 + 37 * i for i in range(32)], [2048] * 7 + [5]]


@pytest.mark.parametrize("warm", [0.0, 0.5, 1.0])
@pytest.mark.parametrize("name", ["qwen2-7b", "starcoder2-3b"])
def test_dense_counts_are_the_flops_counts(name, warm):
    conf = _conf(name)
    s = F.shapes(conf["config"])
    c = families.load(conf).counts(conf["config"])
    for n in (1, 3, 577, 1536):
        assert c.prefill_flops(n) == F.prefill_flops(s, n)
    for ctx in (1, 7, 600, 2048):
        assert c.decode_token_flops(ctx) == F.decode_token_flops(s, ctx)
    peaks = PEAKS["TPU v5 lite"]
    for ctxs in CTXS:
        fl, nbytes = F.paged_attn_cost(s, ctxs, 16, warm)
        got = c.attn_step_cost(ctxs, 16, warm)
        assert got == (s.layers * fl, s.layers * nbytes)
        # the roofline's least time is linear in both
        assert F.least_time(*got, peaks) == pytest.approx(
            s.layers * F.least_time(fl, nbytes, peaks), rel=1e-15)


def test_dense_attn_step_cost_by_hand():
    """qwen2-7b, 14 layers, two lanes of 16 and 17 keys: 1 + 2 hot pages
    of 4 KV heads, K and V, 16 bf16 rows of 128; q and out per lane."""
    conf = _conf("qwen2-7b")
    c = families.load(conf).counts(conf["config"])
    assert c.attn_step_cost([16, 17], 16, 0.0) == (
        14 * 4 * 28 * 128 * 33,
        14 * (3 * 2 * 4 * 16 * 256 + 2 * 28 * 128 * 2 * 2))


def test_dense_draw_is_the_weights_draw():
    seed = 2**33 + 5
    got = jax.tree_util.tree_flatten_with_path(
        families.load(TINY_CONFIG).make_params(TINY_CONFIG["config"], seed))
    want = jax.tree_util.tree_flatten_with_path(
        W.make_params(TINY_CONFIG["config"], seed))
    assert got[1] == want[1]
    for (path, a), (_, b) in zip(got[0], want[0]):
        assert a.dtype == b.dtype, path
        assert np.array_equal(np.asarray(a), np.asarray(b)), path


def test_a_family_module_may_define_dataclasses(tmp_path):
    """A family is loaded from its file as a module of its own, so a
    dataclass defined in it (as a family's counts may be) finds it."""
    where = tmp_path / "bench" / "families"
    where.mkdir(parents=True)
    (where / "with_dataclass.py").write_text(
        "from __future__ import annotations\nimport dataclasses\n\n\n"
        "@dataclasses.dataclass(frozen=True)\nclass Counts:\n"
        "    layers: int\n")
    fam = families.load({"name": "x", "family": "with_dataclass"}, tmp_path)
    assert fam.Counts(layers=3).layers == 3


#: a family that exists only in the test's checkout: dense GQA without
#: q/k/v biases, in the engine, the draw and the reference
NOBIAS = '''"""Dense GQA without q/k/v biases."""
import dataclasses

import jax.numpy as jnp

from bench.families import dense_gqa

BIASES = ("bq", "bk", "bv")


def _attn(params, fn):
    block = params["scan"][0]
    return dict(params, scan=(dict(block, attn=fn(block["attn"])),))


def arch(conf):
    """dense_gqa's architecture without q/k/v biases."""
    return dataclasses.replace(dense_gqa.arch(conf), qkv_bias=False)


def make_params(config, seed):
    """dense_gqa's draw without q/k/v biases."""
    return _attn(dense_gqa.make_params(config, seed), lambda a: {
        k: v for k, v in a.items() if k not in BIASES})


def gaps(config, params, tokens, cand, quantize=None):
    """The dense float32 reference with every q/k/v bias nought."""
    return dense_gqa.gaps(config, _attn(params, lambda a: dict(a, **{
        b: jnp.zeros_like(a["w" + b[1]][:, 0], jnp.float32)
        for b in BIASES})), tokens, cand, quantize)


def counts(config):
    """dense_gqa's counts: a bias costs no matmul."""
    return dense_gqa.counts(config)
'''
#: the same engine fed dense_gqa's draw, biases and all
NOBIAS_DENSE_DRAW = NOBIAS + '''

def make_params(config, seed):
    """dense_gqa's draw, q/k/v biases included."""
    return dense_gqa.make_params(config, seed)
'''
FAMILIES = {"gqa_nobias": NOBIAS, "gqa_nobias_dense_draw": NOBIAS_DENSE_DRAW}
#: cell -> the configuration's family (None: no "family" key)
SEAM_CELLS = {"nobias.chat": "gqa_nobias",
              "dense-draw.chat": "gqa_nobias_dense_draw",
              "missing.chat": "no_such_family",
              "unnamed.chat": None}


@pytest.fixture(scope="module")
def seam_root(tmp_path_factory):
    """The tiny checkout with the test families, a configuration naming
    each, and a cell each; no other file of ``bench/`` is touched."""
    root = make_tiny_root(tmp_path_factory.mktemp("families_checkout"))
    for name, text in FAMILIES.items():
        (root / "bench" / "families" / f"{name}.py").write_text(text)
    man = json.loads((root / "BENCHMARK.json").read_text())
    for cell, fam in SEAM_CELLS.items():
        conf = dict(TINY_CONFIG, name=cell.split(".")[0])
        conf.pop("family")
        if fam is not None:
            conf["family"] = fam
        (root / "bench" / "configs" / f"{conf['name']}.json").write_text(
            json.dumps(conf))
        (root / "bench" / "limits" / f"{cell}.json").write_text(
            json.dumps(TINY_LIMITS))
        man["workloads"].append({"name": cell, "config": conf["name"],
                                 "traffic": "tiny-chat", "chips": 1,
                                 "why": "test"})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root


@pytest.mark.parametrize("cell", sorted(SEAM_CELLS))
def test_a_family_is_a_file_of_its_own(seam_root, cell):
    fam = SEAM_CELLS[cell]
    if fam == "gqa_nobias":
        out = harness.run_cell(cell, 2**32 + 21, 2.0, False,
                               t_start=time.perf_counter(), root=seam_root,
                               require_tpu=False, compile_cache=False)
        assert out["correct"], out["check"]
        assert out["check"]["sampled_tokens"]["value"] > 0
        assert out["failed"] == 0
    elif fam == "gqa_nobias_dense_draw":
        # the engine's tree has no biases: the draw is refused at set-up
        with pytest.raises(ValueError, match="weight layout differs"):
            harness.set_up(cell, 3, root=seam_root, require_tpu=False,
                           compile_cache=False)
    else:
        # found by name or not at all: no default family, no device
        want = (seam_root / "bench" / "families"
                / (f"{fam}.py" if fam else ""))
        with pytest.raises(FileNotFoundError) as e:
            harness.load_cell(cell, seam_root)
        assert str(want).rstrip("/") in str(e.value)
