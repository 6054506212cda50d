"""Model families, one file each: ``bench/families/<family>.py``.

A configuration file names its family under the key ``"family"``. The
family module holds everything the benchmark needs that depends on the
model's architecture, as four functions:

- ``arch(conf) -> ArchConfig``: the program's architecture for the file;
- ``make_params(config, seed)``: every weight, drawn on the device from
  the seed, in the tree the engine takes;
- ``gaps(config, params, tokens, cand, quantize=None)``: the family's
  plain float32 reference, at ``highest`` matmul precision, with the
  ``int8`` / ``fp8`` control (as ``bench.reference.gaps``);
- ``counts(config)``: an object with ``prefill_flops(n)``,
  ``decode_token_flops(ctx)`` and ``attn_step_cost(ctxs, page_size,
  warm_share) -> (flops, bytes)``, the last summed over every attention
  layer of one decode step.
"""
from __future__ import annotations

import importlib.util
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent


def path(conf: dict, root: pathlib.Path | None = None) -> pathlib.Path:
    """The family file a configuration names; raises where the file
    names none or the module is not there."""
    where = (root / "bench" / "families" if root else HERE)
    if "family" not in conf:
        raise FileNotFoundError(
            f"configuration {conf.get('name')!r} names no family: it needs "
            f"a key \"family\" naming {where}/<family>.py")
    p = where / f"{conf['family']}.py"
    if not p.is_file():
        raise FileNotFoundError(
            f"configuration {conf.get('name')!r} names family "
            f"{conf['family']!r}, and there is no module at {p}")
    return p


def load(conf: dict, root: pathlib.Path | None = None):
    """The family module a configuration names, loaded from its file."""
    p = path(conf, root)
    spec = importlib.util.spec_from_file_location(f"bench_family_{p.stem}",
                                                  p)
    mod = importlib.util.module_from_spec(spec)
    # registered before it runs: a dataclass defined in the module looks
    # its module up in sys.modules
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod
