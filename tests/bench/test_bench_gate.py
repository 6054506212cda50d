"""The check that decides ``correct``, driven end to end on the CPU.

Each test runs the whole harness on the tiny cell, past the look for a
chip: set-up, an open-loop window through ``PagedEngine.submit`` and
``PagedEngine.step``, the drain, and the comparison with the float32
reference. A sound run is correct; a run whose timed path is broken
underneath is not.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from bench import harness
from bench_fixtures import TINY_CELL, TINY_CONFIG

SECONDS = 2.0


def _run(root, seed, fault=None):
    return harness.run_cell(TINY_CELL, seed, SECONDS, False,
                            t_start=time.perf_counter(), root=root,
                            require_tpu=False, compile_cache=False,
                            fault=fault)


def test_sound_run_is_correct(tiny_root):
    out = _run(tiny_root, 2**33 + 17)
    assert out["correct"], out["check"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert {"ttft_p95_ms", "itl_p50_ms", "itl_p95_ms", "setup_s",
            "output_tokens_per_s"} <= set(out["metrics"])
    assert list(out)[-1] == "check"
    assert out["device"]["platform"] == "cpu"
    assert out["check"]["sampled_tokens"]["value"] > 0


def _altered_tokens(eng):
    """A token altered where it is produced: the decode step's sampled
    ids shift by one (and feed the next step, as a real fault would)."""
    step, vocab = eng._decode, TINY_CONFIG["config"]["vocab_size"]

    def bad(*args):
        nxt, pools = step(*args)
        return (nxt + 1) % vocab, pools
    bad._cache_size = step._cache_size
    eng._decode = bad


def _state_unchanged(eng):
    """A step that returns its state unchanged: the decode step's KV
    writes are thrown away."""
    step = eng._decode

    def bad(params, pools, *args):
        nxt, _ = step(params, jax.tree.map(jnp.copy, pools), *args)
        return nxt, pools
    bad._cache_size = step._cache_size
    eng._decode = bad


def test_altered_token_is_not_correct(tiny_root):
    out = _run(tiny_root, 2**33 + 17, _altered_tokens)
    assert not out["correct"]
    assert out["check"]["max_gap"]["value"] > \
        out["check"]["max_gap"]["limit"]


def test_unchanged_state_is_not_correct(tiny_root):
    out = _run(tiny_root, 2**33 + 17, _state_unchanged)
    assert not out["correct"]
    assert out["check"]["max_gap"]["value"] > \
        out["check"]["max_gap"]["limit"]


def test_no_chip_means_no_run(tiny_root):
    import pytest
    with pytest.raises(harness.NoChip):
        harness.run_cell(TINY_CELL, 1, SECONDS, False,
                         t_start=time.perf_counter(), root=tiny_root)


def test_fp8_control_is_not_correct_where_the_program_is(tiny_root):
    """The control: the reference one precision step below the bf16
    weights the configuration states (fp8 e4m3, one scale per output
    channel) stands in the program's place at the check. Its tokens fail
    the limit and the run reads not correct, where the program's tokens,
    read on the same sample, keep it. (int8 weights do not separate from
    the program's own bf16 rounding at these sizes; see PERF.md.)"""
    from bench_fixtures import MID_CELL
    for seed in (5, 2**32 + 9):
        out = harness.run_cell(MID_CELL, seed, SECONDS, False,
                               t_start=time.perf_counter(), root=tiny_root,
                               require_tpu=False, compile_cache=False,
                               control="fp8")
        lim = out["check"]["max_gap"]["limit"]
        r = out["diagnostics"]["readings"]
        assert not out["correct"]
        assert out["check"]["max_gap"]["value"] == r["fp8"]["max_gap"]
        assert r["fp8"]["max_gap"] > lim
        assert r["program"]["max_gap"] <= lim
        assert out["check"]["lost"]["value"] == 0


def test_closed_loop_opens_after_a_preemption(tiny_root):
    from bench_fixtures import LD_CELL
    out = harness.run_cell(LD_CELL, 2**31 + 3, SECONDS, False,
                           t_start=time.perf_counter(), root=tiny_root,
                           require_tpu=False, compile_cache=False)
    assert out["correct"], out["check"]
    st = out["diagnostics"]["stats"]
    assert st["preemptions"] > 0 and st["store"]["demote_warm"] > 0
    assert set(out["metrics"]) == {"output_tokens_per_s", "setup_s"}
    assert out["metrics"]["output_tokens_per_s"]["value"] > 0
