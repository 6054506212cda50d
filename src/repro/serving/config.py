"""Declarative serving configuration: ServeConfig + AssistSpec -> engine.

``ServeConfig`` describes WHAT to serve (arch, traffic shape) and nests an
``AssistSpec`` (repro.assist) describing which assist tasks run under it
-- the KV compress site, the paged tier ladder, the prefetch task, the
attention backend.  ``build()`` turns the config into a running engine via
``EngineBase.from_config``, so the dense ``Engine`` and the paged
``PagedEngine`` share ONE construction path instead of divergent
constructor APIs.

The old flat flags (``kv_mode`` / ``paged`` / ``page_size`` /
``hbm_budget_mb`` / ``attn_backend``) are kept as CLI-facing aliases: when
no ``assist`` spec is given they fold into one, and the two spellings
build token-identical engines (tests/test_assist.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax

from repro.assist import AssistSpec
from repro.configs.base import DEFAULT_EOS_ID
from repro.obs import ObsSpec


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Declarative serving configuration (CLI flags map 1:1).

    ``assist`` is authoritative for every assist decision; the flat
    fields below it exist for CLI/backward compatibility and are folded
    into an ``AssistSpec`` when none is passed.
    """
    arch: str
    reduced: bool = False
    requests: int = 8
    slots: int = 4                  # dense: batch slots; paged: decode lanes
    max_len: int = 128
    max_new: int = 12
    seed: int = 0
    # end-of-sequence token both engines honor; same constant the engine
    # constructors default to, so direct construction and build() agree
    eos_id: int = DEFAULT_EOS_ID
    # flat assist aliases (deprecated spelling; see AssistSpec)
    kv_mode: str = "bf16"           # dense engine cache mode (bf16 | int8)
    paged: bool = False
    page_size: int = 16
    hbm_budget_mb: float = 64.0
    attn_backend: str = "gather"
    # max_cold_pages caps the cold page-id space (None = derive from the
    # host budget / HBM pools); threaded through AssistSpec into
    # EngineBase.from_config
    max_cold_pages: Optional[int] = None
    # cross-request prefix reuse (paged engine; DESIGN.md 14): flat
    # aliases of the AssistSpec prefix knobs, same folding rules
    prefix_reuse: bool = False
    prefix_max_nodes: int = 512
    prefix_min_pages: int = 1
    prefix_prefetch: bool = True
    assist: Optional[AssistSpec] = None
    # multi-turn sessions (repro.sessions, DESIGN.md 15): None means the
    # one-shot serving path; ``session_park`` is the flat CLI alias for
    # the spec's park switch (False = stateless re-prefill baseline)
    sessions: Optional[object] = None
    session_park: bool = True
    # observability (repro.obs): counters + execution probe on by default,
    # traces off; None folds to the default ObsSpec in __post_init__
    obs: Optional[ObsSpec] = None
    # resilience (repro.serving.resilience, DESIGN.md 17): bounded
    # admission queue (None = unbounded, SLO-aware shed above it), a
    # FaultSpec for the seeded chaos harness, and the harvest readback
    # stall timeout (None = block forever, the pre-PR behavior)
    max_queue: Optional[int] = None
    fault: Optional[object] = None
    harvest_timeout_s: Optional[float] = None

    def __post_init__(self):
        if self.assist is None:
            object.__setattr__(self, "assist", AssistSpec(
                kv=self.kv_mode, paged=self.paged,
                attn_backend=self.attn_backend, page_size=self.page_size,
                hbm_budget_mb=self.hbm_budget_mb,
                max_cold_pages=self.max_cold_pages,
                prefix_reuse=self.prefix_reuse,
                prefix_max_nodes=self.prefix_max_nodes,
                prefix_min_pages=self.prefix_min_pages,
                prefix_prefetch=self.prefix_prefetch))
        else:
            # an explicit spec is authoritative: back-fill the flat
            # aliases so both spellings always agree (code reading
            # scfg.paged etc. must never contradict scfg.assist)
            spec = self.assist
            for field, value in (("kv_mode", spec.kv),
                                 ("paged", spec.paged),
                                 ("page_size", spec.page_size),
                                 ("hbm_budget_mb",
                                  spec.budget_bytes / 2 ** 20),
                                 ("attn_backend", spec.attn_backend),
                                 ("max_cold_pages", spec.max_cold_pages),
                                 ("prefix_reuse", spec.prefix_reuse),
                                 ("prefix_max_nodes",
                                  spec.prefix_max_nodes),
                                 ("prefix_min_pages",
                                  spec.prefix_min_pages),
                                 ("prefix_prefetch",
                                  spec.prefix_prefetch)):
                object.__setattr__(self, field, value)
        if self.obs is None:
            object.__setattr__(self, "obs", ObsSpec())

    def session_spec(self):
        """The SessionSpec this config serves under (lazy import: the
        sessions package sits ABOVE serving, so config only names it).
        An explicit ``sessions`` spec is authoritative; otherwise the
        flat ``session_park`` alias folds into a default spec."""
        from repro.sessions.spec import SessionSpec
        if self.sessions is not None:
            return self.sessions
        return SessionSpec(park=self.session_park)

    # -- derived configs ------------------------------------------------------

    def tier_config(self):
        """The paged cache's TierConfig, from the assist spec."""
        from repro.cache import TierConfig
        spec = self.assist
        return TierConfig(
            page_size=spec.page_size,
            hbm_budget_bytes=spec.budget_bytes,
            hot_fraction=spec.hot_fraction,
            enable_warm=spec.enable_warm,
            enable_cold=spec.enable_cold,
            host_budget_bytes=spec.host_budget_bytes,
            prefetch_lookahead=spec.prefetch_lookahead,
            pages_per_prefetch_tick=spec.pages_per_prefetch_tick,
            cold_delta=spec.cold_delta,
            async_prefetch=spec.async_prefetch)

    # -- construction ---------------------------------------------------------

    def build(self, model=None, params=None, obs=None):
        """(engine, model, params) for this config.

        ``model``/``params`` may be passed in to share one initialized
        model across several engine configurations (benchmarks do this);
        otherwise they are built from ``arch``/``reduced``/``seed``.
        ``obs`` overrides the engine's Observability bundle (launch/
        serve.py passes one bound to the process-global registry so
        /metrics exports this engine).
        """
        if model is None:
            from repro.configs import get_arch, reduced as reduce_cfg
            from repro.models.model import build_model
            cfg = get_arch(self.arch)
            if self.reduced:
                cfg = reduce_cfg(cfg)
            if not cfg.causal:
                raise SystemExit(f"{cfg.name} is encoder-only: no serving "
                                 f"path")
            model = build_model(cfg)
        if params is None:
            params = model.init(jax.random.PRNGKey(self.seed))
        from repro.serving.engine import EngineBase
        return (EngineBase.from_config(self, model, params, obs=obs),
                model, params)
