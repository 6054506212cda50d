"""How large a page budget a cell's compiled programs leave room for.

    python -m bench.size_budget --workload <cell> --seed <n>

Builds the cell's engine at the budget its configuration states, compiles
the decode step and the largest prefill bucket of the cell's traffic, and
prints their ``memory_analysis()`` with the chip's byte limit. The page
pools grow linearly with the budget, so the largest budget is what the
limit leaves after the weights, the larger program's temporaries and
outputs, and a margin. Run on the chip; the result goes into the
configuration file by hand, with these figures beside it.
"""
from __future__ import annotations

import argparse
import json
import sys

from bench.run import ROOT

MARGIN = 512 << 20


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    from bench import families
    from bench import harness as H
    from repro.models.model import build_model, prompt_bucket
    from repro.serving.engine import stage_host

    cell, conf, mix, _ = H.load_cell(args.workload)
    fam = families.load(conf)
    H.device_info(cell["chips"])
    model = build_model(fam.arch(conf))
    params = fam.make_params(conf["config"], args.seed)
    eng = H.build_engine(conf, mix, params, model, args.seed)
    ps, max_len = eng.pool.page_size, mix["serve"]["max_len"]

    def mem(compiled):
        m = compiled.memory_analysis()
        return {k: int(getattr(m, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")}

    dec = mem(eng._decode.lower(
        eng.params, eng.store.pools, eng._tokens_dev, eng._bt_dev,
        stage_host(eng._lengths), stage_host(eng._state_slots),
        stage_host(eng._temps), eng.rng, 1).compile())
    bucket = prompt_bucket(mix["prompt"]["max"], max_len, ps)
    batch = eng._pad_prompt(list(range(2, bucket)), ps)
    pre = mem(eng._prefill.lower(eng.params, batch, 0.0, eng.rng,
                                 0).compile())
    w_bytes = sum(a.nbytes for a in jax.tree.leaves(params))
    pool_bytes = sum(a.nbytes for a in jax.tree.leaves(eng.store.pools))
    limit = jax.devices()[0].memory_stats()["bytes_limit"]
    need = max(dec["temp_size_in_bytes"] + dec["output_size_in_bytes"]
               - dec["alias_size_in_bytes"],
               pre["temp_size_in_bytes"] + pre["output_size_in_bytes"])
    budget = conf["serving"]["hbm_budget_bytes"]
    room = limit - w_bytes - pool_bytes - need - MARGIN
    print(json.dumps({
        "bytes_limit": limit, "weights_bytes": w_bytes,
        "pool_bytes": pool_bytes, "budget_bytes": budget,
        "decode": dec, "prefill_bucket": bucket, "prefill": pre,
        "margin_bytes": MARGIN,
        "largest_budget_bytes": int((budget + room) // (1 << 20)) << 20,
        "hot_pages": eng.store.hot_pages,
        "warm_pages": eng.store.warm_pages}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
