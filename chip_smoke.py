"""Chip smoke test: serve qwen2-7b at its published widths on one TPU.

    python chip_smoke.py [--seed N]

Drives the serving main path -- ``ServeConfig.build()`` -> ``PagedEngine``,
the path ``repro.launch.serve`` takes -- on one chip, with random weights
drawn from ``--seed``.  The model keeps every published width of qwen2-7b
(d_model 3584, 28 heads / 4 KV heads, d_ff 18944, vocab 152064, QKV bias)
and is cut in depth only: 14 of 28 layers, one period of its ("attn",)
pattern, because all 28 layers hold 15.23 GB of bf16 parameters and leave
no room for a page pool on a 16 GB chip.

  A  kernels: the paged Pallas decode kernels against the gather reference
     at serving shapes (hot-only bf16 pages; hot bf16 + warm int8 pages).
  B  serve: 48 requests through the tiered KV cache at a 3 GiB page budget
     with the int8 warm tier on, attention backend ``pallas_int8``.
  C  tier pressure: 24 requests on 2 lanes under a budget near a quarter
     of the KV they need, so pages demote hot -> warm -> cold (host-packed) and
     promote back.

Every phase checks its results and raises on a failure.  The last line of
standard output is one JSON object, printed only when every phase passed:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
Without a TPU the script exits non-zero before running anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

ARCH = "qwen2-7b"
N_LAYERS = 14             # one ("attn",) period of 28: depth is the only cut
PAGE = 16
LANES = 16
MAX_LEN = 2048
MAX_NEW = 32
POOL_PAGES = 2048         # phase A: pool slots per tier
TABLE_PAGES = 256         # phase A: pages per block-table row
# bf16 kernel output against the f32 gather reference: two bf16 roundings
# of values well under 1, plus the online softmax's summation order
KERNEL_ATOL = 1e-2


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or incomplete result."""


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


def log(msg: str):
    print(msg, flush=True)


def phase_kernels(cfg, seed: int):
    """Phase A: the paged Pallas backends the engine calls, against the
    gather backend on the same pools and block tables, at ``cfg``'s head
    shapes."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attn import ops

    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    B, G, D = LANES, cfg.n_kv_heads, cfg.head_dim
    group, P, NP = cfg.n_heads // G, POOL_PAGES, TABLE_PAGES
    shp = (1 + P, G, PAGE, D)
    pools = {
        "kh": jax.random.normal(ks[0], shp, jnp.bfloat16),
        "vh": jax.random.normal(ks[1], shp, jnp.bfloat16),
        "k8": jax.random.randint(ks[2], shp, -127, 128).astype(jnp.int8),
        "v8": jax.random.randint(ks[3], shp, -127, 128).astype(jnp.int8),
        "ks": jax.random.uniform(ks[4], shp[:3], jnp.float32, 0.002, 0.02),
        "vs": jax.random.uniform(ks[5], shp[:3], jnp.float32, 0.002, 0.02),
    }
    q = jax.random.normal(ks[6], (B, G * group, D), jnp.bfloat16)
    hot_bt = jax.random.randint(ks[7], (B, NP), 1, P + 1, jnp.int32)
    warm = jax.random.bernoulli(ks[8], 0.5, (B, NP))
    tiered_bt = jnp.where(warm, -hot_bt, hot_bt)   # <0: warm slot -loc
    lengths = jax.random.randint(ks[9], (B,), 1, NP * PAGE + 1, jnp.int32)
    cases = (("bf16 hot-only", ops.attn_backend_pallas, hot_bt, False),
             ("tiered hot+warm int8", ops.attn_backend_pallas_int8,
              tiered_bt, True))
    for name, kernel, bt, has_warm in cases:
        run = jax.jit(lambda q, p, bt, ln, f=kernel, w=has_warm:
                      f(q, p, bt, ln, has_warm=w))
        ref = jax.jit(lambda q, p, bt, ln, w=has_warm:
                      ops.attn_backend_gather(q, p, bt, ln, has_warm=w))
        t0 = time.perf_counter()
        got = jax.block_until_ready(run(q, pools, bt, lengths))
        compile_s = time.perf_counter() - t0
        want = ref(q, pools, bt, lengths)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        t0 = time.perf_counter()
        for _ in range(10):
            got = run(q, pools, bt, lengths)
        jax.block_until_ready(got)
        ms = (time.perf_counter() - t0) / 10 * 1e3
        log(f"  {name}: max |kernel - gather| = {err:.3e} "
            f"(tolerance {KERNEL_ATOL:.0e}); first call incl. compile "
            f"{compile_s:.2f} s; {ms:.3f} ms/call over {B} lanes x "
            f"{NP} pages (chip reading)")
        check(np.isfinite(err) and err <= KERNEL_ATOL,
              f"phase A {name}: max error {err} exceeds {KERNEL_ATOL}")


def _prompts(seed: int, n: int, lo: int, hi: int, vocab: int) -> list:
    rng = np.random.default_rng(seed)
    return [rng.integers(2, vocab, int(L)).tolist()
            for L in rng.integers(lo, hi + 1, n)]


def phase_serve(model, params, *, label: str, seed: int, lanes: int,
                max_len: int, max_new: int, prompts: list,
                budget_bytes: int) -> dict:
    """Serve ``prompts`` through ``ServeConfig.build()``, check every
    output, and return the engine's stats."""
    import jax
    from repro.analysis.runtime import assert_compile_bound
    from repro.assist import AssistSpec
    from repro.models.model import n_prompt_buckets, prompt_bucket
    from repro.serving.config import ServeConfig
    from repro.serving.engine import Request

    cfg = model.cfg
    spec = AssistSpec(paged=True, attn_backend="pallas_int8", page_size=PAGE,
                      hbm_budget_bytes=budget_bytes, enable_warm=True,
                      enable_cold=True)
    scfg = ServeConfig(arch=cfg.name, slots=lanes, max_len=max_len,
                       max_new=max_new, requests=len(prompts), seed=seed,
                       assist=spec)
    t0 = time.perf_counter()
    eng, _, _ = scfg.build(model=model, params=params)
    log(f"  engine built in {time.perf_counter() - t0:.2f} s: "
        f"{eng.store.hot_pages} hot + {eng.store.warm_pages} warm pages "
        f"in {budget_bytes / 2**30:.3f} GiB, {lanes} lanes")

    # warm-up: one short request per prefill bucket the traffic uses, so
    # the measured window runs on compiled programs
    rid = 0
    firsts = {}
    for p in prompts:
        firsts.setdefault(prompt_bucket(len(p), max_len, PAGE), p)
    t0 = time.perf_counter()
    for p in firsts.values():
        eng.submit(Request(rid=rid, prompt=p, max_new=2))
        rid += 1
    eng.run()
    warm_s = time.perf_counter() - t0
    log(f"  warm-up (compiles {len(firsts)} prefill buckets + decode): "
        f"{warm_s:.2f} s")

    reqs = []
    for p in prompts:
        reqs.append(Request(rid=rid, prompt=p, max_new=max_new))
        eng.submit(reqs[-1])
        rid += 1
    tok0 = eng.tokens_generated
    t0 = time.perf_counter()
    eng.run()
    eng.sync()
    wall = time.perf_counter() - t0
    st = eng.stats()
    n_tok = eng.tokens_generated - tok0
    log(f"  {len(reqs)} requests, {n_tok} decode tokens in {wall:.2f} s: "
        f"{n_tok / wall:.1f} tokens/s (chip reading, host wall clock)")

    check(not eng.queue, f"{label}: {len(eng.queue)} requests never admitted")
    for r in reqs:
        check(r.done and r.error is None,
              f"{label}: request {r.rid} ended with error {r.error!r}")
        check(len(r.out) == max_new or (r.out and r.out[-1] == eng.eos_id),
              f"{label}: request {r.rid} stopped after {len(r.out)} tokens "
              f"without EOS")
        check(all(0 <= t < cfg.vocab_size for t in r.out),
              f"{label}: request {r.rid} has a token outside [0, vocab)")
    check(st["quarantines"] == 0, f"{label}: {st['quarantines']} quarantines")
    assert_compile_bound(label, eng.prefill_compiles(),
                         n_prompt_buckets(max_len, PAGE))
    log(f"  prefill compiles {eng.prefill_compiles()} "
        f"(bound {n_prompt_buckets(max_len, PAGE)}); tiers {st['tiers']}; "
        f"store {st['store']}; peak resident tokens "
        f"{st['peak_resident_tokens']}")
    mem = jax.devices()[0].memory_stats() or {}
    log(f"  peak_bytes_in_use {mem.get('peak_bytes_in_use')} "
        f"bytes_in_use {mem.get('bytes_in_use')}")
    return st


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights, pools and prompts")
    args = ap.parse_args(argv)

    import jax
    platform = jax.default_backend()
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{platform!r}")
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    from repro.configs import get_arch
    from repro.models.model import build_model
    from repro.models.transformer import paged_geometry
    from repro.roofline.peaks import chip_peaks

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    peaks = chip_peaks(dev)
    log(f"device {device}; published peaks: {peaks.bf16_flops:.3g} FLOP/s "
        f"bf16, {peaks.hbm_bw:.3g} B/s HBM, {peaks.hbm_bytes:.3g} B "
        f"({peaks.source}); compile cache {cache}")

    full = get_arch(ARCH)
    cfg = dataclasses.replace(full, n_layers=N_LAYERS)
    log(f"model {ARCH}: d_model {cfg.d_model}, {cfg.n_heads} heads / "
        f"{cfg.n_kv_heads} KV heads, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab_size}; {N_LAYERS} of {full.n_layers} layers (depth "
        f"cut only), random weights from seed {args.seed}")

    log("phase A: paged Pallas kernels vs gather reference")
    phase_kernels(cfg, args.seed)

    t0 = time.perf_counter()
    model = build_model(cfg)
    # jitted so each weight's f32 draw, scale and bf16 cast fuse: drawn op
    # by op, the d_ff-wide layer stack holds two f32 copies at peak
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(args.seed)))
    n_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    log(f"params {n_bytes / 1e9:.3f} GB initialised in "
        f"{time.perf_counter() - t0:.2f} s (incl. compile)")

    log("phase B: serve through ServeConfig.build() -> PagedEngine")
    prompts = _prompts(args.seed, 48, 100, 1000, cfg.vocab_size)
    phase_serve(model, params, label="phase B", seed=args.seed, lanes=LANES,
                max_len=MAX_LEN, max_new=MAX_NEW, prompts=prompts,
                budget_bytes=3 << 30)
    gc.collect()           # the phase B engine's pools leave the chip

    log("phase C: tier pressure (budget ~1/4 of the traffic's KV)")
    # prompts short enough that the biggest still prefills into the small
    # hot tier, and two lanes, so the lanes' own (protected) pages leave
    # hot room to admit requests beyond them: their pages are what goes
    # down the ladder (with four lanes the lanes fill hot and admission
    # waits instead, and nothing reaches the cold tier)
    prompts = _prompts(args.seed + 1, 24, 100, 300, cfg.vocab_size)
    need = sum(len(p) + MAX_NEW for p in prompts)
    hot_tok = paged_geometry(cfg, PAGE).hot_page_bytes // PAGE
    st = phase_serve(model, params, label="phase C", seed=args.seed,
                     lanes=2, max_len=MAX_LEN, max_new=MAX_NEW,
                     prompts=prompts, budget_bytes=need * hot_tok // 4)
    s = st["store"]
    check(s["demote_warm"] > 0 and s["demote_cold"] > 0,
          f"phase C: no demotion down the ladder ({s})")
    check(s["promote_warm"] > 0 and s["promote_hot"] > 0,
          f"phase C: no promotion back up ({s})")
    log(f"  cold tier round trip: {s['demote_cold']} pages packed to host, "
        f"{s['promote_warm']} unpacked back; cold bytes now "
        f"{st['cold_bytes']}")

    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
