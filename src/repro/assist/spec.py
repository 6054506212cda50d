"""AssistSpec -- the declarative assist configuration (DESIGN.md 11).

One frozen dataclass names every assist decision a deployment makes, for
every task kind, instead of the scattered flags the engines and train
loop used to take (``kv_mode``, ``attn_backend``, tier knobs,
grad-compress scheme).  ``ServeConfig`` and ``TrainConfig`` nest one;
``ServeConfig.build()`` / ``EngineBase.from_config()`` turn it into a
running engine, ``make_train_step`` into a compiled step.

The spec is configuration only: it never imports the cache/serving/
training layers, so every layer can consume it without cycles.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class AssistSpec:
    """Which assist tasks run, where, and with what knobs.

    Serving -- the KV compress site (paper 5) and its paged tier ladder:
      kv               DENSE-engine cache mode: "bf16" | "int8".  The
                       paged engine ignores it: there the int8 site is
                       the warm tier (enable_warm), hot pages stay bf16
      paged            page the KV cache (repro.cache) instead of slots
      attn_backend     paged decode attention impl (kernels/decode_attn)
      page_size        tokens per page
      hbm_budget_mb    HBM budget for the page pools (MiB)
      hbm_budget_bytes exact-byte override of hbm_budget_mb
      hot_fraction     share of the HBM budget kept bf16
      enable_warm      int8 warm tier (the CABA KV site)
      enable_cold      packed host cold tier
      host_budget_bytes  cold-tier budget (None = unbounded)
      max_cold_pages   hard cap on cold page ids (None = derive from the
                       host budget / HBM pools)
      cold_delta       delta-along-sequence transform before cold packing
      use_roofline_trigger  let the AWC trigger gate demotion

    Prefetch task (paper 8.2):
      prefetch_lookahead       ticks-to-finish that arms the WaSP lookahead
      pages_per_prefetch_tick  promotion budget cap per tick
      async_prefetch           overlap promotion via async device_put

    Training sites:
      grads      grad-collective scheme: "raw" | "int8" | "fp8"
      grad_axis  mesh axis the compressed collective crosses
      opt_state  optimizer-moment storage: "raw" | "int8"

    Memoize task (paper 8.1):
      memoize               enable LUT memoization where a consumer asks
      memoize_min_hit_rate  controller floor before self-disable

    Prefix reuse (paper 8.1 lifted to the cache layer, DESIGN.md 14):
      prefix_reuse      radix-tree prefix store at paged-engine admission
                        (refcounted read-only page sharing + COW)
      prefix_max_nodes  radix-tree node budget (one page held per node)
      prefix_min_pages  shortest shareable prefix, in full pages
      prefix_prefetch   route cold matched radix pages through the WaSP
                        prefetch queue ahead of the prefill dispatch
                        (counted on ``prefetch_issued_total{kind=prefix}``)
    """
    # serving / KV compress site
    kv: str = "bf16"
    paged: bool = False
    attn_backend: str = "gather"
    page_size: int = 16
    hbm_budget_mb: float = 64.0
    hbm_budget_bytes: Optional[int] = None
    hot_fraction: float = 0.5
    enable_warm: bool = True
    enable_cold: bool = True
    host_budget_bytes: Optional[int] = None
    max_cold_pages: Optional[int] = None
    cold_delta: bool = True
    use_roofline_trigger: bool = True
    # prefetch task
    prefetch_lookahead: int = 2
    pages_per_prefetch_tick: int = 2
    async_prefetch: bool = True
    # training sites
    grads: str = "raw"
    grad_axis: str = "pod"
    opt_state: str = "raw"
    # memoize task
    memoize: bool = False
    memoize_min_hit_rate: float = 0.25
    # prefix-reuse task (memoize kind, paged engine only)
    prefix_reuse: bool = False
    prefix_max_nodes: int = 512
    prefix_min_pages: int = 1
    prefix_prefetch: bool = True

    def __post_init__(self):
        if self.prefix_max_nodes < 1:
            raise ValueError("prefix_max_nodes must be >= 1")
        if self.prefix_min_pages < 1:
            raise ValueError("prefix_min_pages must be >= 1")
        if self.kv not in ("bf16", "int8"):
            raise ValueError(f"kv must be bf16|int8, got {self.kv!r}")
        if self.grads not in ("raw", "int8", "fp8"):
            raise ValueError(f"grads must be raw|int8|fp8, got {self.grads!r}")
        if self.opt_state not in ("raw", "int8"):
            raise ValueError(f"opt_state must be raw|int8, "
                             f"got {self.opt_state!r}")

    @property
    def budget_bytes(self) -> int:
        if self.hbm_budget_bytes is not None:
            return int(self.hbm_budget_bytes)
        return int(self.hbm_budget_mb * 2 ** 20)

    def build_memoizer(self, fn, d_out: int, **kw):
        """Live ``Memoizer`` honoring this spec's memoize switches, or
        ``None`` when the task is off -- the entry point a step function
        uses to consult the spec instead of hard-coding LUT knobs.

        An explicitly passed ``controller`` is authoritative (its own
        ``min_hit_rate`` wins over ``memoize_min_hit_rate``) -- callers
        sharing one controller across tasks configured the floor there."""
        if not self.memoize:
            return None
        from repro.assist.controller import AssistController
        from repro.assist.memoize import Memoizer
        ctl = kw.pop("controller", None) or AssistController(
            min_hit_rate=self.memoize_min_hit_rate)
        return Memoizer(fn, d_out, controller=ctl, **kw)
