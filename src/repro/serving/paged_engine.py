"""Paged serving engine: block tables + tiered KV cache (DESIGN.md 10.4).

Differences from the dense ``engine.Engine``:

* Decode state lives in fixed-size pages owned by ``repro.cache`` instead
  of per-slot ``[B, max_len]`` slabs -- short requests hold short block
  tables, so no HBM is spent on padding.  Every decode-state page KIND is
  covered (repro.assist.page_kinds): per-head attention KV, the
  absorbed-MLA latent (DeepSeek-V2), and the fixed-size recurrence state
  of mamba2/rwkv6 layers, which is parked as ONE non-growing slab per
  request.
* ``lanes`` bounds how many requests DECODE per tick (the jit batch), but
  *residency* is bounded only by the HBM/host budgets: requests beyond the
  lane count are admitted (prefilled into pages) and parked, their pages
  demoted down the tier ladder by LRU -- preemption-by-demotion instead of
  rejection.
* The roofline trigger (cache/policy.py) decides whether demotion
  (compression) is allowed at all, per the paper's AWC discipline.

The decode tick is HOST-SYNC-FREE (DESIGN.md 12) -- the CABA discipline
(assist work must hide in the main computation's shadow, paper 4.2/6)
applied to the host itself:

* sampling runs ON DEVICE inside the jitted step (per-lane temperature
  vector + threaded PRNG key as jit inputs); the sampled tokens feed the
  next tick without ever visiting the host;
* the block table and last-token vector are DEVICE-RESIDENT between
  ticks, updated by dirty-row scatters only when a lane's assignment or
  page placement actually changed (store.drain_dirty);
* lane retirement reads the PREVIOUS tick's tokens (one-tick-lagged
  ``jax.device_get``) while the current tick executes.  EOS discovery
  lags one tick -- the lane decodes one junk token that the next harvest
  discards (requests that exhaust ``max_new`` free their lane at dispatch
  with no lag, since the budget is host-known);
* prompt lengths BUCKET to page-size multiples rounded up to powers of
  two, so prefill compiles O(log(max_len / page_size)) variants instead
  of one per distinct prompt length;
* tier movement accumulates into batched movers (cache/tiers.py): an
  eviction storm lands in O(1) dispatches.

``host_sync=True`` reconstructs the pre-PR loop (exact-length prefill,
blocking per-tick readback, full block-table rebuild, single-page movers)
for A/B measurement in benchmarks/serving_micro.py::run_host_overhead.

With every tier but hot disabled and enough budget, outputs are
token-identical to the dense engine on the same prompts (tests/
test_paged_engine.py, test_paged_kinds.py); the tiered configs trade
bounded int8 error on parked requests for >= 2x resident-token capacity
(benchmarks/serving_micro.py).
"""
from __future__ import annotations

import collections
import concurrent.futures
import dataclasses
import functools
import time
from typing import Optional, Union

import numpy as np
import jax
import jax.numpy as jnp

from repro.analysis.runtime import tick_guard
from repro.assist import AssistController
from repro.assist.page_kinds import page_kind
from repro.cache import (BlockPool, CachePolicy, TierConfig,
                         TieredKVStore, TIER_COLD, TIER_WARM,
                         decode_roofline_terms)
from repro.cache.block_pool import PREFIX_RID, PoolExhausted
from repro.cache.policy import kv_site, warm_ratio
from repro.cache.tiers import ColdPageCorrupt
from repro.configs.base import DEFAULT_EOS_ID
from repro.models import ssm as SSM
from repro.models import transformer as T
from repro.models.model import ModelFns
from repro.obs import Observability
from repro.obs.trace import no_span
from repro.obs.metrics import TOKENS_BUCKETS
from repro.serving.engine import EngineBase, Request, stage_host
from repro.serving.resilience import (FaultInjector, Watchdog, read_snapshot,
                                      restore_engine, snapshot_engine,
                                      write_snapshot)


@dataclasses.dataclass
class _RState:
    """A resident request: its tokens so far and decode progress.

    ``last_tok`` is the request's latest sampled token: a host int once
    harvested, or a device scalar while the sample is still in flight
    (fresh admission) -- either feeds the token-injection scatter when the
    request enters a lane.

    ``forced`` is the teacher-forcing queue of a RESUMED session turn
    (DESIGN.md 15): known tokens (the turn's prompt, plus the parked
    history's one uncached tail token) that are fed through the decode
    step to grow the cache WITHOUT re-prefilling history.  While it is
    non-empty the model's samples are discarded, the budget does not
    advance, and the next tick's input comes from this queue.
    """
    req: Request
    length: int          # tokens whose KV is in the cache (incl. in-flight)
    last_tok: Union[int, jax.Array]
    remaining: int
    forced: collections.deque = dataclasses.field(
        default_factory=collections.deque)


@jax.jit
def _scatter_rows(dst, idx, rows):
    """Dirty-row update of a device-resident per-lane array.  ``idx`` is
    padded with an out-of-range lane index; ``mode="drop"`` discards the
    padding instead of clipping it onto a real row.  NOT donated: ``dst``
    may also be the in-flight harvest handle (the previous tick's sampled
    tokens), which must stay readable until its lagged device_get."""
    return dst.at[idx].set(rows, mode="drop")


class PagedEngine(EngineBase):
    """Continuous batching over a paged, tiered KV cache."""

    def __init__(self, model: ModelFns, params, *, lanes: int, max_len: int,
                 tier: Optional[TierConfig] = None,
                 eos_id: int = DEFAULT_EOS_ID, seed: int = 0,
                 controller: Optional[AssistController] = None,
                 use_roofline_trigger: bool = True,
                 max_cold_pages: Optional[int] = None,
                 backend: str = "gather",
                 host_sync: bool = False,
                 prefix_reuse: bool = False,
                 prefix_max_nodes: int = 512,
                 prefix_min_pages: int = 1,
                 prefix_prefetch: bool = True,
                 max_queue: Optional[int] = None,
                 fault=None,
                 harvest_timeout_s: Optional[float] = None,
                 obs: Optional[Observability] = None):
        self.obs = obs if obs is not None else Observability()
        # engine.* phase spans (repro.obs.trace); with telemetry off every
        # span is one shared no-op context
        self._span = (self.obs.tracer.span if self.obs.tracer is not None
                      else no_span)
        # strict mode wraps the jitted tick dispatch in a transfer guard
        # (DESIGN.md 16); OFF shares one no-op context -- fence-free
        self._strict_transfers = bool(self.obs.spec.strict_transfers)
        self._tick_guard = tick_guard(self._strict_transfers)
        cfg = model.cfg
        bad = T.paged_unsupported_layers(cfg)
        if bad:
            raise ValueError(f"{cfg.name}: paged decode unsupported for "
                             f"layers {bad}")
        self.model, self.params, self.cfg = model, params, cfg
        self.backend = backend
        tier = tier or TierConfig()
        if max_len % tier.page_size:
            raise ValueError("max_len must be a multiple of page_size")
        self.max_len, self.eos_id = max_len, eos_id
        self.n_lanes = lanes
        self.maxp = max_len // tier.page_size
        self.host_sync = host_sync
        self.prefix_prefetch = prefix_prefetch
        self.bucket_prefill = not host_sync
        self.segments = T.paged_segments(cfg)
        geom = T.paged_geometry(cfg, tier.page_size)
        self.geom = geom
        self.has_state = geom.has_state
        if any(s.page_kind == "mla_latent" for s in self.segments):
            # latent pages have a reduced backend table (gather-only until
            # the TPU pass): fail at construction, not inside a jit trace
            from repro.kernels.decode_attn import ops as attn_ops
            attn_ops.get_latent_backend(backend)

        # budget split: state slabs are carved out first (each decoding
        # lane NEEDS its slab hot, plus one for swap-in headroom); token
        # pages split what is left per the tier fractions
        budget = tier.hbm_budget_bytes
        hot_state = warm_state = max_cold_state = 0
        if self.has_state:
            hot_state = lanes + 1
            if tier.enable_warm:
                warm_state = max(2 * lanes, 2)
            if tier.enable_cold:
                max_cold_state = 8 * (hot_state + warm_state)
            budget = max(0, budget - hot_state * geom.state_hot_bytes
                         - warm_state * geom.state_warm_bytes)
        if geom.hot_page_bytes:
            hot, warm = tier.split_pages(geom.hot_page_bytes,
                                         geom.warm_page_bytes, budget=budget)
            if max_cold_pages is None:
                if tier.enable_cold:
                    max_cold_pages = (
                        tier.host_budget_bytes // geom.warm_page_bytes
                        if tier.host_budget_bytes else 8 * (hot + warm))
                else:
                    max_cold_pages = 0
        else:
            # attention-free stack (pure SSM/RWKV): token pages hold zero
            # bytes and exist only for block-table bookkeeping -- size the
            # slot space to the state-bounded residency
            hot = max(1, hot_state + warm_state + max_cold_state) * self.maxp
            warm, max_cold_pages = 0, 0
        num_pages = (hot + warm + max_cold_pages
                     + hot_state + warm_state + max_cold_state)
        # ONE registry threads through pool/store/policy/controller so the
        # whole engine exports a single metric namespace (DESIGN.md 13)
        metrics = self.obs.metrics
        self.pool = BlockPool(num_pages, tier.page_size, metrics=metrics)
        self.store = TieredKVStore(geom, num_pages, hot_pages=hot,
                                   warm_pages=warm, hot_state=hot_state,
                                   warm_state=warm_state,
                                   host_budget_bytes=tier.host_budget_bytes,
                                   cold_delta=tier.cold_delta,
                                   metrics=metrics)
        if host_sync:
            self.store.mover_batch = 1      # pre-PR per-page dispatches
        terms = site = None
        if use_roofline_trigger:
            # resident-token estimate for the trigger: tokens the hot tier
            # can actually hold.  Attention-free stacks' token slots are
            # zero-byte bookkeeping (hot is inflated on purpose), so there
            # residency is bounded by the hot STATE slots instead.
            resident_est = (hot * tier.page_size if geom.hot_page_bytes
                            else hot_state * max_len)
            # page-kind-aware per-token bytes: MLA latents / hybrid stacks
            # hold far less than the dense-GQA formula; the state slab is
            # amortized over a full-length request
            per_tok = (geom.hot_page_bytes / tier.page_size
                       + geom.state_hot_bytes / max_len)
            terms = decode_roofline_terms(cfg, lanes, resident_est,
                                          kv_bytes=per_tok)
            site = kv_site(cfg, resident_est, kv_bytes=per_tok)
        self.policy = CachePolicy(tier, controller=controller
                                  or AssistController(metrics=metrics),
                                  terms=terms, site=site,
                                  measured_ratio=warm_ratio(cfg.head_dim),
                                  metrics=metrics)

        # cross-request prefix reuse (DESIGN.md 14): a radix-tree prefix
        # store mapping known prompt-prefix pages read-only into new
        # lanes' block tables.  Only token-page kinds that declare
        # ``shareable`` participate; a stack with state slabs still
        # shares token pages (dedup) but never skips prefill (the slab
        # is only produced by running it).
        self.prefix = None
        self.prefix_decision = None
        self._shareable = all(page_kind(s.page_kind).shareable
                              for s in self.segments
                              if page_kind(s.page_kind).grows)
        if prefix_reuse and self._shareable and geom.hot_page_bytes:
            from repro.assist.registry import REGISTRY
            task = REGISTRY.get("prefix", "memoize")
            self.prefix = task.build(
                pool=self.pool, max_nodes=prefix_max_nodes,
                min_pages=prefix_min_pages,
                controller=self.policy.controller, metrics=metrics)
            if use_roofline_trigger:
                # SITE-LOCAL plan: the admission step the skip relieves
                # is prefill (compute-dominant by construction), not the
                # decode tick; a typical prompt is modeled at half max_len
                n_active = float(cfg.active_param_count())
                ptoks = max(max_len // 2, tier.page_size)
                psite = self.prefix.admission_site(n_active, ptoks)
                self.prefix_decision = self.prefix.plan(
                    psite, self.prefix.admission_terms(n_active, ptoks))
                if not self.prefix_decision.enabled:
                    self.prefix.enabled = False

        # engine-level series (handles bound once; no-ops when obs is off)
        self._c_tokens = metrics.counter(
            "engine_tokens_generated_total", "decode tokens harvested")
        self._c_preempt = metrics.counter(
            "engine_preemptions_total",
            "lane preemptions (resident request demoted back to parked)")
        self._c_admit = metrics.counter(
            "engine_admissions_total", "requests admitted (prefilled)")
        self._c_retire = metrics.counter(
            "engine_retirements_total", "requests retired (EOS or budget)")
        self._h_bucket = metrics.histogram(
            "engine_prefill_bucket_tokens",
            "padded prompt-bucket length per prefill", TOKENS_BUCKETS)
        self._g_lanes = metrics.gauge(
            "engine_lanes_active", "lanes decoding this tick")
        self._g_parked = metrics.gauge(
            "engine_parked", "resident requests parked without a lane")
        self._g_queued = metrics.gauge(
            "engine_queued", "requests waiting for admission")
        self._g_resident = metrics.gauge(
            "engine_resident_tokens", "tokens whose decode state is cached")
        self._c_pskips = metrics.counter(
            "engine_prefill_skips_total",
            "admissions whose prefill was skipped on a full prefix hit")
        self._c_pskip_tokens = metrics.counter(
            "engine_prefill_skipped_tokens_total",
            "prompt tokens never prefilled (covered by shared pages)")
        self._c_pshared = metrics.counter(
            "engine_prefix_shared_pages_total",
            "prefix-store pages mapped read-only into admitted requests")
        # session lifecycle (DESIGN.md 15): parked conversations keep
        # their pages across retirements and resume by forced replay
        self._c_parks = metrics.counter(
            "engine_session_parks_total",
            "retired requests parked as sessions (pages kept)")
        self._c_resumes = metrics.counter(
            "engine_session_resumes_total",
            "parked sessions resumed without history re-prefill")
        self._c_replayed = metrics.counter(
            "engine_replayed_tokens_total",
            "known tokens teacher-forced through the decode step on resume")
        self._g_parked_sessions = metrics.gauge(
            "engine_parked_sessions",
            "sessions parked between turns (pages resident, no request)")
        # resilience (DESIGN.md 17): seeded fault injection, quarantine
        # accounting, and the degradation watchdog with hysteresis
        self.fault = (FaultInjector(fault, metrics=metrics)
                      if fault is not None else None)
        self._watchdog = Watchdog(metrics=metrics)
        self._degraded = False
        self._alloc_fault = False
        self.harvest_timeout_s = harvest_timeout_s
        self._hpool: Optional[concurrent.futures.ThreadPoolExecutor] = None
        self._c_quarantine = {r: metrics.counter(
            "engine_quarantines_total",
            "requests retired with error status and pages scrubbed "
            "after an unrecoverable fault", reason=r)
            for r in ("checksum", "nan")}

        self.lanes: list[Optional[int]] = [None] * lanes
        self.resident: dict[int, _RState] = {}
        self.parked: collections.deque[int] = collections.deque()
        self.queue: collections.deque[Request] = collections.deque()
        self.finished: list[Request] = []
        self._park_on_retire: set[int] = set()
        self._parked_sessions: dict[int, int] = {}   # rid -> cached length
        self._session_history: dict[int, list] = {}  # rid -> full token log
        self.rng = jax.random.PRNGKey(seed)
        self._init_intake(metrics=metrics, max_queue=max_queue)
        self.tick_no = 0
        self.peak_resident_tokens = 0
        self.tokens_generated = 0
        self.admission_blocked = False

        # device-resident per-lane tick state + host mirrors.  The device
        # copies update by dirty-row scatter; the host mirrors exist so a
        # dirty row can be rebuilt without touching the clean ones.
        self._bt_host = np.zeros((lanes, self.maxp), np.int32)
        self._bt_dev = jnp.zeros((lanes, self.maxp), jnp.int32)
        self._tokens_dev = jnp.zeros((lanes,), jnp.int32)
        self._lengths = np.zeros(lanes, np.int32)
        self._temps = np.zeros(lanes, np.float32)
        self._state_slots = np.zeros(lanes, np.int32)
        self._dirty_bt: set[int] = set()
        self._dirty_tok: set[int] = set()
        self._inflight: Optional[tuple] = None   # (tokens, snapshot)
        self._pending_first: list = []           # [(req, token handle)]

        # the warm gather/dequant is compiled out entirely when the warm
        # tier is disabled (block tables then never hold negative entries);
        # sampling is fused so the tick never returns logits to the host
        def step_fn(params, pools, tokens, bt, lengths, state_slots, temps,
                    rng, tick):
            logits, pools = model.paged_decode_step(
                params, pools, tokens[:, None], bt, lengths, state_slots,
                has_warm=warm > 0, backend=backend)
            key = jax.random.fold_in(
                jax.random.fold_in(rng, self.DECODE_STREAM), tick)
            nxt = self._select_token(logits[:, 0], temps, key)
            return nxt, pools

        self._decode = jax.jit(step_fn, donate_argnums=(1,))

        # paged_layout keeps local-attention prefill KV at absolute
        # positions (no rolling compaction) so it scatters into pages.
        # The cache is sized to the BUCKET (padded prompt length), not to
        # max_len: write_prefill scatters exactly the bucket's pages.
        ps = tier.page_size

        def prefill_fn(params, batch, temp, rng, salt):
            pad_to = -(-batch["tokens"].shape[1] // ps) * ps
            logits, state = model.prefill(params, batch, pad_to,
                                          moe_dropless=True, kv_mode="bf16",
                                          paged_layout=True)
            tl = batch["true_len"]
            last = jnp.take_along_axis(logits, (tl - 1)[:, None, None],
                                       axis=1)[:, 0]
            temps = jnp.broadcast_to(jnp.asarray(temp, jnp.float32),
                                     (last.shape[0],))
            key = jax.random.fold_in(
                jax.random.fold_in(rng, self.PREFILL_STREAM), salt)
            tok = self._select_token(last, temps, key)
            return tok, state

        self._prefill = jax.jit(prefill_fn)

    # -- request lifecycle ---------------------------------------------------

    @staticmethod
    def _state_rid(rid: int) -> int:
        """Block-pool owner id of a request's state-slab page.  Kept
        disjoint from request rids (>= 0) and the pool's free marker (-1)
        so the slab never interleaves with the token-page block table."""
        return -2 - rid

    def submit(self, req: Request):
        # fail fast at the API boundary: an oversize request can never be
        # admitted, and surfacing it mid-run would strand in-flight work
        if len(req.prompt) + req.max_new > self.max_len:
            self._c_rejected["oversize"].inc()
            raise ValueError(
                f"request {req.rid}: prompt ({len(req.prompt)}) + max_new "
                f"({req.max_new}) exceeds max_len ({self.max_len})")
        req.t_submit = time.perf_counter()
        super().submit(req)

    def resident_tokens(self) -> int:
        return sum(r.length for r in self.resident.values())

    def pending_decode_tokens(self) -> int:
        """In-flight decode tokens that WILL be appended at the next
        harvest (junk rows of already-retired requests excluded) -- the
        lag correction benchmark windows add to ``tokens_generated``."""
        if self._inflight is None:
            return 0
        return sum(1 for _, rid, _, keep in self._inflight[1]
                   if keep and rid in self.resident)

    def _touch(self, rid: int):
        self.pool.touch(rid, self.tick_no)
        if self.has_state:
            self.pool.touch(self._state_rid(rid), self.tick_no)

    def _segment_kv(self, one_state):
        """Per GROWING segment (k, v) [stack, G, S, width] from a B=1
        prefill state, in :func:`repro.models.transformer.paged_segments`
        order.  MLA segments map (latent c, rope r) onto the (k, v)
        planes with one head."""
        out = []
        for seg in self.segments:
            if seg.page_kind == "state_slab":
                continue
            if seg.name.startswith("pat_"):
                st = one_state["scan"][int(seg.name[4:])]
                peel = lambda a: a[:, 0]               # drop B=1
            else:                     # head_i / tail_i: B=1 leading == stack
                st = one_state[seg.name]
                peel = lambda a: a
            if seg.page_kind == "mla_latent":
                out.append((peel(st["c"])[:, None], peel(st["r"])[:, None]))
            else:
                out.append((peel(st["k"]), peel(st["v"])))
        return out

    def _segment_state(self, one_state):
        """Per STATE segment, the flattened recurrence slab f32[stack, W]
        from a B=1 prefill state."""
        slabs = []
        for seg in self.segments:
            if seg.page_kind != "state_slab":
                continue
            if seg.name.startswith("pat_"):
                st = one_state["scan"][int(seg.name[4:])]
                st = jax.tree.map(lambda a: a[:, 0], st)   # drop B=1
            else:
                st = one_state[seg.name]
            slabs.append(SSM.flatten_state(self.cfg, seg.kind, st))
        return slabs

    def _protected(self) -> set[int]:
        """Pages this tick's decode will touch (lane requests)."""
        prot: set[int] = set()
        for rid in self.lanes:
            if rid is not None:
                prot.update(self.pool.table(rid))
                if self.has_state:
                    prot.update(self.pool.table(self._state_rid(rid)))
        return prot

    # -- lane bookkeeping (device-resident tick state) -----------------------

    def _assign(self, i: int, rid: int):
        """Put ``rid`` into lane ``i``: the row rebuild and token
        injection are deferred to the pre-dispatch dirty-row scatter."""
        self.lanes[i] = rid
        self._dirty_bt.add(i)
        self._dirty_tok.add(i)

    def _vacate(self, i: int):
        """Empty lane ``i``: its block-table row gathers from trash and
        its write lands on the trash page until reassigned."""
        self.lanes[i] = None
        self._bt_host[i, :] = 0
        self._lengths[i] = 0
        self._temps[i] = 0.0
        self._state_slots[i] = 0
        self._dirty_bt.add(i)
        self._dirty_tok.discard(i)

    def _push_lane_updates(self):
        """Incremental device update of the block table / token vector.

        Host-side row rebuilds (the per-page encoded_loc walk) happen
        ONLY for rows whose lane assignment or page placement changed,
        and a steady tick dispatches nothing at all.  When any row IS
        dirty, the scatter ships a fixed-shape [lanes, maxp] operand
        (padded, ``mode="drop"``) so every dirty count shares one
        compiled program -- dirtiness saves dispatches and host work,
        not transfer bytes on the (rare) dirty ticks."""
        moved = self.store.drain_dirty()
        if moved:
            lane_of = {rid: i for i, rid in enumerate(self.lanes)
                       if rid is not None}
            for pid in moved:
                # a shared page maps into EVERY reader's block-table row:
                # one physical move dirties all of them (the prefix
                # store's own shadow ref has no lane)
                for r in self.pool.owners_of(pid):
                    if r == PREFIX_RID:
                        continue
                    rid = r if r >= 0 else -2 - r
                    i = lane_of.get(rid)
                    if i is not None:
                        self._dirty_bt.add(i)
        if self.host_sync:                   # pre-PR loop: rebuild all
            self._dirty_bt.update(i for i, rid in enumerate(self.lanes)
                                  if rid is not None)
        if not self._dirty_bt and not self._dirty_tok:
            return
        if self._dirty_bt:
            idx = np.full(self.n_lanes, self.n_lanes, np.int32)
            rows = np.zeros((self.n_lanes, self.maxp), np.int32)
            for j, i in enumerate(sorted(self._dirty_bt)):
                rid = self.lanes[i]
                if rid is not None:
                    st = self.resident[rid]
                    table = self.pool.table(rid)
                    self._bt_host[i, :] = 0
                    self._bt_host[i, :len(table)] = \
                        [self.store.encoded_loc(p) for p in table]
                    self._lengths[i] = st.length
                    self._temps[i] = st.req.temperature
                    if self.has_state:
                        spid = self.pool.table(self._state_rid(rid))[0]
                        self._state_slots[i] = self.store.state_hot_slot(spid)
                idx[j] = i
                rows[j] = self._bt_host[i]
            self._bt_dev = _scatter_rows(self._bt_dev, jnp.asarray(idx),
                                         jnp.asarray(rows))
            self._dirty_bt.clear()
        if self._dirty_tok:
            tidx = np.full(self.n_lanes, self.n_lanes, np.int32)
            vals: list = []
            for j, i in enumerate(sorted(self._dirty_tok)):
                tidx[j] = i
                tok = self.resident[self.lanes[i]].last_tok
                vals.append(tok if isinstance(tok, jax.Array)
                            else jnp.asarray(tok, jnp.int32))
            vals += [jnp.asarray(0, jnp.int32)] * (self.n_lanes - len(vals))
            self._tokens_dev = _scatter_rows(
                self._tokens_dev, jnp.asarray(tidx),
                jnp.stack(vals).astype(jnp.int32))
            self._dirty_tok.clear()

    # -- admission (preemption-by-demotion, never rejection) -----------------

    def _consult_prefix(self, req: Request,
                        protected: set[int]) -> Optional[list[int]]:
        """Prefix-store consult (DESIGN.md 14): the pages of the longest
        cached prefix of ``req.prompt`` (empty without a store, or under
        the degraded plan, which pauses prefix admission), or None when
        the matched prefix itself is poisoned."""
        if self.prefix is None or self._degraded:
            return []
        matched = self.prefix.match(req.prompt)
        self._release_prefix_pages()
        if self.prefix_prefetch and matched:
            # predictive WaSP re-promotion: matched radix pages that sit
            # cold go through the prefetch queue AHEAD of the prefill
            # dispatch, instead of promoting on first touch
            cold_m = [p for p in matched if self.store.tier[p] == TIER_COLD]
            if cold_m:
                self.policy.schedule_prefetch(cold_m, kind="prefix")
                try:
                    self.policy.drain_prefetch(self.pool, self.store,
                                               protected)
                except ColdPageCorrupt as e:
                    # scrub it; admission retries next tick with a fresh
                    # match
                    self._quarantine_page(e.pid, "checksum")
                    return None
                self.policy.account_swap_in(
                    matched, [p for p in cold_m
                              if self.store.tier[p] == TIER_COLD])
        return matched

    def _admit_one(self, req: Request, protected: set[int]) -> bool:
        """Admit one request into pages: prefill it, or take a full
        prefix match.  The ``engine.admit`` span opens once the prefix
        consult knows how many pages are shared."""
        if self._alloc_fault:
            # injected allocator exhaustion (FaultSpec "alloc"): surfaces
            # exactly like real pool pressure -- admission blocks this
            # tick and is retried on the next (retry is sound here)
            self._alloc_fault = False
            raise PoolExhausted("injected allocator exhaustion")
        # matched pages map into the new table READ-ONLY via pool.share --
        # they consume no free pages and no prefill work.  When the match
        # covers every prompt position but the last, prefill is skipped
        # outright and the first tick plays the final prompt token as a
        # decode step.
        matched = self._consult_prefix(req, protected)
        if matched is None:
            return False
        plen = len(req.prompt)
        with self._span("admit", rid=req.rid, prompt_len=plen,
                        shared_pages=len(matched)):
            if not self._place(req, matched, protected):
                return False
        req.t_admit = time.perf_counter()
        return True

    def _place(self, req: Request, matched: list[int],
               protected: set[int]) -> bool:
        """Pages for an admitted request, and its prefill unless the
        match covers the prompt; False when the budgets hold no room."""
        plen = len(req.prompt)
        ps = self.pool.page_size
        npg = self.pool.pages_for(plen)
        n_own = npg - len(matched)
        full_skip = (bool(matched) and not self.has_state
                     and len(matched) * ps >= plen - 1)
        if n_own + (1 if self.has_state else 0) > self.pool.n_free:
            return False
        if n_own and not self.policy.make_hot_room(
                self.pool, self.store, protected, n=n_own):
            return False
        if self.has_state and not self.policy.make_hot_room(
                self.pool, self.store, protected, cls="state"):
            return False
        for p in matched:                        # table[:m] = shared prefix
            self.pool.share(p, req.rid)
            protected.add(p)
        self._c_pshared.inc(len(matched))
        pages = self.pool.allocate(req.rid, n_own) if n_own else []
        slots = [self.store.place_hot(p) for p in pages]
        spid = None
        if self.has_state:
            spid = self.pool.allocate(self._state_rid(req.rid), 1)[0]
            self.store.place_hot_state(spid)
        if full_skip:
            # every position 0..plen-2 is already cached; the first tick
            # feeds prompt[-1] as the lane token, writes its KV (COW if
            # that page is shared) and samples the first output token
            self.resident[req.rid] = _RState(req, plen - 1,
                                             int(req.prompt[plen - 1]),
                                             req.max_new)
            self._c_pskips.inc()
            self._c_pskip_tokens.inc(plen)
            if self.obs.tracer is not None:
                self.obs.tracer.instant("prefix_hit", rid=req.rid,
                                        shared_pages=len(matched),
                                        skipped=plen)
        else:
            # partial (or no) match: full prefill runs -- its recomputed
            # KV for matched positions scatters into the trash slot, the
            # tail lands in this request's own pages.  Token identity is
            # the caller's own prefill logits; the shared pages hold
            # bit-identical KV by causality + pad-invariant bucketing.
            batch = self._pad_prompt(req.prompt, ps)
            with self._span("prefill", rid=req.rid,
                            bucket=batch["tokens"].shape[1]):
                tok, one_state = self._prefill(self.params, batch,
                                               float(req.temperature),
                                               self.rng, req.rid)
                self.store.write_prefill([0] * len(matched) + slots,
                                         self._segment_kv(one_state),
                                         S=plen)
                if spid is not None:
                    self.store.write_state(spid,
                                           self._segment_state(one_state))
            # the sampled first token stays on device; it is appended to
            # req.out (and becomes a host int) at the next harvest
            self.resident[req.rid] = _RState(req, plen, tok[0],
                                             req.max_new - 1)
            self._pending_first.append((req, tok))
        if self.prefix is not None and not self._degraded:
            # publish this prompt's own full pages for future admissions
            self.prefix.insert(req.prompt, self.pool.table(req.rid))
            self._release_prefix_pages()
        self._c_admit.inc()
        self._touch(req.rid)
        self.peak_resident_tokens = max(self.peak_resident_tokens,
                                        self.resident_tokens())
        return True

    def _release_prefix_pages(self):
        """Release tier storage of pages whose LAST reference dropped
        inside the prefix store (node eviction / self-disable)."""
        rel = self.prefix.drain_released()
        if rel:
            for pid in rel:
                self.store.release(pid)
            self.policy.forget_pages(rel)

    def drop_prefix_cache(self):
        """Drop every prefix-store reference (drain helper: after this,
        retiring all requests returns the pool to fully free)."""
        if self.prefix is not None:
            self.prefix.drop_all()
            self._release_prefix_pages()

    # -- lane maintenance ----------------------------------------------------

    def _ensure_decodable(self, rid: int, protected: set[int]) -> bool:
        """All of rid's pages gatherable, its write page AND its state slab
        hot; may allocate the next page at a page boundary.  The request's
        own pages join ``protected`` up front so making room for one of
        them can never evict another.

        The whole walk runs as ONE ``store.deferred()`` mover episode
        (DESIGN.md 16 ownership discipline): the state-slab promotion,
        the write-page re-promotion and the COW copy coalesce into
        batched dispatches with whatever the policy's room-making evicts,
        instead of landing as single-page movers between them.  Tier
        bookkeeping stays eager inside the episode, so every decision
        below reads up-to-date tiers; the device copies land at episode
        exit, before ``step``'s pre-dispatch ``flush_movers``."""
        with self.store.deferred():
            st = self.resident[rid]
            table = self.pool.table(rid)
            protected.update(table)
            if self.has_state:
                spid = self.pool.table(self._state_rid(rid))[0]
                protected.add(spid)
                if self.store.tier[spid] == TIER_COLD:
                    if not self.policy.make_warm_room(self.pool, self.store,
                                                      protected,
                                                      cls="state"):
                        return False
                    self.store.promote_to_warm(spid)
                else:
                    self.store.commit_page(spid)
                if self.store.tier[spid] == TIER_WARM:
                    if not self.policy.make_hot_room(self.pool, self.store,
                                                     protected,
                                                     cls="state"):
                        return False
                    self.store.promote_to_hot(spid)
            need = self.pool.pages_for(st.length + 1)
            while len(table) < need:
                if self.pool.n_free < 1 or not self.policy.make_hot_room(
                        self.pool, self.store, protected):
                    return False
                pid = self.pool.allocate(rid, 1)[0]
                self.store.place_hot(pid)
                protected.add(pid)
                table = self.pool.table(rid)
            cold = [p for p in table if self.store.tier[p] == TIER_COLD]
            if cold:
                # swap-in promotion for the whole cold run in ONE batched
                # episode (the session-resume path can carry a full parked
                # history here) instead of K blocking unpack+write calls
                if not self.policy.make_warm_room(self.pool, self.store,
                                                  protected, n=len(cold)):
                    return False
                if len(self.store.promote_many(cold)) != len(cold):
                    return False
            for pid in table:
                if self.store.tier[pid] != TIER_COLD:
                    # page may have been async-promoted THIS tick (after
                    # the tick-start barrier): land it before the gather
                    # reads it
                    self.store.commit_page(pid)
            wp = table[st.length // self.pool.page_size]
            if self.store.tier[wp] == TIER_WARM:
                if not self.policy.make_hot_room(self.pool, self.store,
                                                 protected):
                    return False
                self.store.promote_to_hot(wp)
            if self.pool.is_shared(wp):
                # copy-on-write divergence (DESIGN.md 14): this tick
                # WRITES the incoming token's KV into ``wp``, which other
                # readers (sibling lanes / the prefix store) see
                # read-only.  Break it out into a private hot copy first;
                # the shared original keeps its slot, so no other
                # reader's row dirties.
                if self.pool.n_free < 1 or not self.policy.make_hot_room(
                        self.pool, self.store, protected):
                    return False
                new = self.pool.cow(rid, wp)
                self.store.place_hot(new)
                self.store.copy_hot(wp, new)
                protected.add(new)
            return True

    def _try_decodable(self, rid: int, protected: set[int]) -> bool:
        """``_ensure_decodable`` with checksum-failure containment: a
        corrupt cold page quarantines every owner of that page (retired
        with error status, pages scrubbed) instead of propagating -- the
        fault never reaches peer lanes or the prefix store."""
        try:
            return self._ensure_decodable(rid, protected)
        except ColdPageCorrupt as e:
            self._quarantine_page(e.pid, "checksum")
            return False

    def _fill_lanes(self, protected: set[int]):
        for i, rid in enumerate(self.lanes):
            if rid is not None:
                continue
            # parked residents first (FIFO), then fresh admissions.  Walk
            # past un-swappable candidates so a stuck head-of-line request
            # cannot starve decodable ones behind it.
            skipped: list[int] = []
            while self.parked:
                cand = self.parked.popleft()
                if cand not in self.resident:
                    continue
                all_pages = list(self.pool.table(cand))
                if self.has_state:
                    all_pages.append(self.pool.table(
                        self._state_rid(cand))[0])
                cold_before = [p for p in all_pages
                               if self.store.tier[p] == TIER_COLD]
                if self._try_decodable(cand, protected):
                    # account once, on the attempt that actually swaps in
                    self.policy.account_swap_in(all_pages, cold_before)
                    self._assign(i, cand)
                    break
                if cand in self.resident:          # no room this tick
                    skipped.append(cand)           # (vs quarantined: gone)
            self.parked.extendleft(reversed(skipped))
            if self.lanes[i] is not None:
                continue
            if self.queue:
                req = self.queue[0]
                try:
                    ok = self._admit_one(req, protected)
                except PoolExhausted:
                    ok = False
                if ok and self._try_decodable(req.rid, protected):
                    self.queue.popleft()
                    self._assign(i, req.rid)
                elif ok:
                    self.queue.popleft()
                    if req.rid in self.resident:   # not quarantined
                        self.parked.append(req.rid)
                else:
                    self.admission_blocked = True

    def _admit_extra(self, protected: set[int]):
        """Admit beyond the lane count: prefill into pages and park.
        Residency is bounded by the budgets, not by the lane count."""
        while self.queue:
            req = self.queue[0]
            try:
                ok = self._admit_one(req, protected)
            except PoolExhausted:
                ok = False
            if not ok:
                self.admission_blocked = True
                return
            self.queue.popleft()
            self.parked.append(req.rid)

    # -- main loop -----------------------------------------------------------

    def step(self) -> bool:
        """One tick: drain barrier, prefetch, schedule, admit, decode
        (sampling fused on device), then harvest the PREVIOUS tick's
        tokens while this tick executes.

        Each phase runs in an ``engine.*`` span (repro.obs.trace): lanes,
        admit (nesting prefill), movers, dispatch, harvest (nesting
        harvest_wait), all inside ``engine.step``."""
        self.tick_no += 1
        span = self._span
        with span("step", tick=self.tick_no,
                  lanes=self.n_lanes - self.lanes.count(None)):
            self.admission_blocked = False
            t_wall = time.perf_counter()
            n_comp = self._jit_compiles()
            fi = self.fault
            if fi is not None:
                # seeded fault sites drawn once per tick (storm-window
                # gated)
                if fi.should("alloc", self.tick_no):
                    self._alloc_fault = True
                if (fi.should("cold_payload", self.tick_no)
                        and self.store.cold):
                    pids = sorted(self.store.cold.keys())
                    self.store.corrupt_cold(
                        pids[fi.pick("cold_payload", len(pids))])
            with span("lanes"):
                protected = self._maintain_lanes()
            self._admit_extra(protected)
            active = [i for i, rid in enumerate(self.lanes)
                      if rid is not None]
            self._g_lanes.set(len(active))
            self._g_parked.set(len(self.parked))
            self._g_queued.set(len(self.queue))
            if not active:
                prev, self._inflight = self._inflight, None
                with span("harvest"):
                    got = self._harvest(prev)
                self._feed_watchdog(t_wall, n_comp)
                return got

            with span("movers"):
                self._push_lane_updates()
                # pending tier copies precede the read
                self._flush_movers_guarded()
            probe = self.obs.probe
            with span("dispatch"):
                # stage every host mirror ABOVE the transfer guard: the
                # guarded region must issue zero implicit h2d copies.  The
                # tick counter is staged only in strict mode -- a python
                # int (weak type) and an int32 device scalar hash to
                # different jit cache entries, so conditional staging
                # keeps one compile per mode
                lengths = stage_host(self._lengths)
                state_slots = stage_host(self._state_slots)
                temps = stage_host(self._temps)
                tick = (jnp.asarray(self.tick_no, jnp.int32)
                        if self._strict_transfers else self.tick_no)
                t0 = time.perf_counter() if probe is not None else 0.0
                with self._tick_guard():
                    nxt, pools = self._decode(self.params, self.store.pools,
                                              self._tokens_dev, self._bt_dev,
                                              lengths, state_slots, temps,
                                              self.rng, tick)
                if probe is not None:
                    probe.record_dispatch(time.perf_counter() - t0)
            if probe is not None and probe.should_fence(self.tick_no):
                # execution-true sample: drain the device queue through
                # this tick (dispatch start -> result ready, backlog
                # included -- it is what a request actually waits)
                # sync-ok: every-Nth execution-true probe fence
                jax.block_until_ready(nxt)
                probe.record_exec(time.perf_counter() - t0)
            self.store.pools = pools
            self._tokens_dev = nxt

            snapshot = []
            closing = 0
            for i in active:
                rid = self.lanes[i]
                st = self.resident[rid]
                st.length += 1              # host-known: the write position
                self._lengths[i] += 1
                if st.forced:
                    # resumed-session replay: the cache just absorbed a
                    # KNOWN token's KV; next tick's input comes from the
                    # replay queue, the model's sample is discarded at
                    # harvest (keep=False) and the budget does not advance
                    st.last_tok = st.forced.popleft()
                    self._dirty_tok.add(i)
                    snapshot.append((i, rid, st.remaining, False))
                    continue
                st.remaining -= 1           # budget advance at dispatch
                snapshot.append((i, rid, st.remaining, True))
                if st.remaining <= 0:
                    # budget exhausted (no readback needed): free the lane
                    # now; the final token is in flight, retires at harvest
                    self._vacate(i)
                if st.remaining <= self.policy.cfg.prefetch_lookahead:
                    closing += 1
            res = self.resident_tokens()
            self.peak_resident_tokens = max(self.peak_resident_tokens, res)
            self._g_resident.set(res)
            if self.host_sync:
                prev, self._inflight = (nxt, snapshot), None
            else:
                prev, self._inflight = self._inflight, (nxt, snapshot)
            with span("harvest"):
                self._harvest(prev)
            # WaSP lookahead: start promoting the next parked requests'
            # cold TOKEN pages -- and their cold state slabs -- while the
            # closing lanes finish, so swap-in promotion hides behind
            # decode ticks
            for rid in list(self.parked)[:max(closing, 0)]:
                cold = [p for p in self.pool.table(rid)
                        if self.store.tier[p] == TIER_COLD]
                if self.has_state:
                    spid = self.pool.table(self._state_rid(rid))[0]
                    if self.store.tier[spid] == TIER_COLD:
                        cold.append(spid)
                if cold:
                    self.policy.schedule_prefetch(cold, kind="lookahead")
            self._feed_watchdog(t_wall, n_comp)
            return True

    def _maintain_lanes(self) -> set[int]:
        """Land last tick's prefetches, fill free lanes, and make every
        lane's request decodable; returns the pages this tick's decode
        touches (``protected``)."""
        # drain barrier: land last tick's async prefetch promotions BEFORE
        # anything can read the warm pool this tick (assist prefetch task)
        self.store.commit_promotions()
        protected = self._protected()
        try:
            self.policy.drain_prefetch(self.pool, self.store, protected)
        except ColdPageCorrupt as e:
            self._quarantine_page(e.pid, "checksum")
        self._fill_lanes(protected)
        # lane maintenance: boundary page allocation / re-promotion for
        # requests that stayed in their lane across ticks.  A lane whose
        # EOS is still in flight runs this too: if its junk token lands on
        # a page boundary this allocates (and may evict for) a page the
        # next harvest frees -- bounded at one page per EOS-at-boundary,
        # accepted in exchange for never blocking on the token value
        for i, rid in enumerate(self.lanes):
            if rid is not None and not self._try_decodable(rid, protected):
                if rid not in self.resident:
                    continue                  # quarantined: lane vacated
                self._vacate(i)                    # preempt by demotion
                self.parked.appendleft(rid)
                self._c_preempt.inc()
                if self.obs.tracer is not None:
                    self.obs.tracer.instant("preempt", rid=rid, lane=i)
        return protected

    def _harvest(self, prev) -> bool:
        """Land the lagged tokens (one device_get, overlapping the tick
        dispatched just before it): append to output streams, update
        last_tok, retire EOS/out-of-budget requests."""
        firsts, self._pending_first = self._pending_first, []
        if prev is None and not firsts:
            return False
        handles = [t for _, t in firsts] + ([prev[0]] if prev else [])
        with self._span("harvest_wait"):
            vals = self._device_get(handles)
        now = time.perf_counter()
        for (req, _), v in zip(firsts, vals):
            tok = int(np.asarray(v).ravel()[0])
            req.t_first = now
            req.out.append(tok)
            st = self.resident.get(req.rid)
            if st is not None and isinstance(st.last_tok, jax.Array):
                st.last_tok = tok
        if prev is not None:
            nxt = np.asarray(vals[-1])
            fi = self.fault
            if fi is not None and fi.should("nan", self.tick_no):
                # simulate NaN logits: the fused sampler's argmax over a
                # NaN row lands out of vocab range -- poison one live lane
                live = [i for i, rid, _, keep in prev[1]
                        if keep and rid in self.resident]
                if live:
                    nxt = nxt.copy()
                    nxt[live[fi.pick("nan", len(live))]] = -1
            for i, rid, rem, keep in prev[1]:
                st = self.resident.get(rid)
                if st is None:
                    continue              # retired earlier: junk past EOS
                if not keep:
                    continue              # replay tick: sample discarded
                tok = int(nxt[i])
                if not 0 <= tok < self.cfg.vocab_size:
                    # unrecoverable (the bad sample is already the next
                    # tick's input): retire with error, scrub pages
                    self._quarantine(rid, "nan")
                    continue
                if not st.req.out:          # a full prefix match's first
                    st.req.t_first = now
                st.req.out.append(tok)
                st.last_tok = tok
                self.tokens_generated += 1
                self._c_tokens.inc()
                self._touch(rid)
                if rem <= 0 or tok == self.eos_id:
                    self._retire(rid)
        return True

    def _retire(self, rid: int):
        st = self.resident.pop(rid)
        st.req.done = True
        self.finished.append(st.req)
        self._c_retire.inc()
        if self.obs.tracer is not None:
            self.obs.tracer.instant("retire", rid=rid,
                                    out_tokens=len(st.req.out))
        for i, r in enumerate(self.lanes):
            if r == rid:
                self._vacate(i)
        if rid in self._park_on_retire:
            # session park (DESIGN.md 15): KEEP every page this rid owns
            # -- token pages, MLA latents, state slab, shared-prefix refs
            # -- so the next turn resumes against the cached history.
            # ``st.length`` is exactly the number of cached positions
            # (the prompt+output prefix whose KV the store holds).
            self._park_on_retire.discard(rid)
            self._parked_sessions[rid] = st.length
            # full token log (prompt + outputs across every turn): what a
            # durable snapshot needs to rebuild the resume replay stream
            base = self._session_history.pop(rid, None)
            if base is None:
                base = list(st.req.prompt)
            self._session_history[rid] = base + list(st.req.out)
            self._c_parks.inc()
            self._g_parked_sessions.set(len(self._parked_sessions))
            if self.obs.tracer is not None:
                self.obs.tracer.instant("session_park", rid=rid,
                                        cached_len=st.length)
            return
        self._session_history.pop(rid, None)
        freed = self.pool.free_request(rid)
        if self.has_state:
            freed += self.pool.free_request(self._state_rid(rid))
        for pid in freed:
            self.store.release(pid)
        self.policy.forget_pages(freed)

    # -- resilience (DESIGN.md 17) -------------------------------------------

    def _jit_compiles(self) -> int:
        return self._prefill._cache_size() + self._decode._cache_size()

    def _feed_watchdog(self, t_wall: float, n_comp: int):
        """Feed one tick's wall latency to the watchdog -- UNLESS this
        tick compiled a new jit variant (first-tick decode, a fresh
        prefill bucket): compile time is a one-off, not load, and must
        not trip the degraded plan."""
        if self._jit_compiles() != n_comp:
            return
        if self._watchdog.observe(time.perf_counter() - t_wall,
                                  self.tick_no):
            self._apply_degraded(self._watchdog.degraded)

    def _flush_movers_guarded(self):
        """Pre-dispatch mover flush under fault injection: a simulated
        dispatch failure retries with exponential backoff (sound -- the
        flush is idempotent until bookkeeping observes it), bounded by
        the spec.  The backoff sleeps inflate tick wall latency, which is
        exactly what feeds the watchdog during a dense storm."""
        fi = self.fault
        if fi is not None and fi.should("mover", self.tick_no):
            spec = fi.spec
            for attempt in range(spec.max_retries):
                fi.note_retry("mover")
                if spec.backoff_base_s > 0.0:
                    time.sleep(spec.backoff_base_s * (2 ** attempt))
                if not fi.should("mover", self.tick_no):
                    break
        self.store.flush_movers()

    def _device_get(self, handles):
        """The harvest readback, with an optional stall watchdog: when
        ``harvest_timeout_s`` is set, a hung dispatch surfaces as a
        watchdog trip carrying the tick id instead of a silent hang --
        then blocks for the value anyway (integrity over latency)."""
        if self.harvest_timeout_s is None:
            # sync-ok: lagged harvest -- overlaps the in-flight tick
            return jax.device_get(handles)
        if self._hpool is None:
            self._hpool = concurrent.futures.ThreadPoolExecutor(
                max_workers=1)
        fut = self._hpool.submit(jax.device_get, handles)
        try:
            return fut.result(timeout=self.harvest_timeout_s)
        except concurrent.futures.TimeoutError:
            if self._watchdog.trip(self.tick_no, "harvest_timeout"):
                self._apply_degraded(True)
            return fut.result()

    def _apply_degraded(self, flag: bool):
        """Flip the degraded plan across the assist stack: prefetch off,
        compression ratio floor relaxed, prefix admission paused."""
        self._degraded = flag
        self.policy.set_degraded(flag)
        self.policy.controller.set_degraded(flag)
        if self.obs.tracer is not None:
            self.obs.tracer.instant("degraded" if flag else "recovered",
                                    tick=self.tick_no)

    def _quarantine(self, rid: int, reason: str):
        """Retire ``rid`` with error status and scrub every page it owns:
        the blast radius of an unrecoverable fault is exactly one rid."""
        st = self.resident.pop(rid, None)
        for i, r in enumerate(self.lanes):
            if r == rid:
                self._vacate(i)
        self._park_on_retire.discard(rid)
        self._parked_sessions.pop(rid, None)
        self._session_history.pop(rid, None)
        try:
            self.parked.remove(rid)
        except ValueError:
            pass
        freed = self.pool.free_request(rid)
        if self.has_state:
            freed += self.pool.free_request(self._state_rid(rid))
        for pid in freed:
            self.store.release(pid)
        self.policy.forget_pages(freed)
        if st is not None:
            st.req.error = reason
            st.req.done = True
            self.finished.append(st.req)
        self._c_quarantine[reason].inc()
        self._g_parked_sessions.set(len(self._parked_sessions))
        if self.obs.tracer is not None:
            self.obs.tracer.instant("quarantine", rid=rid,
                                    reason=reason)

    def _quarantine_page(self, pid: int, reason: str):
        """Scrub every reader of a poisoned page: lane/parked rids are
        quarantined, prefix-store references drop their whole subtree
        (descendant pages extend past the corrupt prefix)."""
        rids: set[int] = set()
        drop_prefix = False
        for r in list(self.pool.owners_of(pid)):
            if r == PREFIX_RID:
                drop_prefix = True
            else:
                rids.add(r if r >= 0 else -2 - r)
        if drop_prefix and self.prefix is not None:
            self.prefix.drop_pid(pid)
            self._release_prefix_pages()
        for r in sorted(rids):
            self._quarantine(r, reason)

    def persist(self, path: str):
        """Durable park: serialize every parked session and the prefix
        tree to ``path`` (atomic write+rename, versioned, per-page CRC).
        Requires a drained engine -- see ``launch/serve.py``'s SIGTERM
        handler for the stop-admission / finish-ticks sequence."""
        write_snapshot(path, snapshot_engine(self))

    def restore(self, path: str):
        """Rebuild parked sessions, pool refcounts and the prefix tree
        from a snapshot into this freshly built engine; conservation is
        re-asserted via ``BlockPool.check()``."""
        restore_engine(self, read_snapshot(path))

    # -- session lifecycle (DESIGN.md 15) ------------------------------------

    def park_on_retire(self, rid: int):
        """Mark a request (queued or resident) so its retirement parks
        the session: every page it owns stays allocated, recorded under
        ``_parked_sessions`` for a later :meth:`resume_session`.  Call
        AFTER ``submit`` -- submit may recycle a colliding rid."""
        self._park_on_retire.add(rid)

    def parked_session_len(self, rid: int) -> int:
        """Cached positions a parked session holds (the prompt+output
        prefix whose decode state is still in the store)."""
        return self._parked_sessions[rid]

    def session_pages(self, rid: int) -> list[int]:
        """Every page a (parked or resident) session owns: token pages
        in table order plus the state slab."""
        pages = list(self.pool.table(rid))
        if self.has_state:
            pages += list(self.pool.table(self._state_rid(rid)))
        return pages

    def park_session_pages(self, rid: int) -> int:
        """Push a parked session's pages down the tier ladder NOW (one
        batched-mover episode) instead of waiting for LRU pressure --
        frees hot capacity for live traffic during the turn gap."""
        if rid not in self._parked_sessions:
            raise KeyError(f"rid {rid} is not parked")
        return self.policy.park_pages(self.pool, self.store,
                                      self.session_pages(rid),
                                      self._protected())

    def prefetch_session(self, rid: int):
        """Predictive re-promotion ahead of the next turn (the WaSP
        prefetch idea lifted from pages to sessions): queue the parked
        session's cold pages so promotion hides behind current decode."""
        if rid not in self._parked_sessions:
            return
        cold = [p for p in self.session_pages(rid)
                if self.store.tier[p] == TIER_COLD]
        if cold:
            self.policy.schedule_prefetch(cold, kind="session")

    def resume_session(self, req: Request, replay):
        """Resume a parked session WITHOUT re-prefilling its history.

        ``req.rid`` must be the parked rid.  ``replay`` is the token
        stream the cache has NOT seen: ``history[cached_len:]`` (zero or
        one tail token, depending on how the previous turn retired) plus
        the new turn's tokens -- at least one token, since the decode
        step needs an input.  Replay tokens are teacher-forced through
        the decode step (the budget does not advance); sampling resumes
        after the last one.  The request joins the parked deque and
        competes for a lane like any resident request."""
        rid = req.rid
        hlen = self._parked_sessions.pop(rid)
        replay = [int(t) for t in replay]
        if not replay:
            raise ValueError("resume needs >= 1 replay token")
        hist = self._session_history.pop(rid, None)
        if hist is not None:
            # cached positions + everything replayed = full known log;
            # this turn's sampled tokens append at the next park
            self._session_history[rid] = hist[:hlen] + replay
        if hlen + len(replay) + req.max_new > self.max_len:
            raise ValueError(
                f"session {rid}: history ({hlen}) + replay "
                f"({len(replay)}) + max_new ({req.max_new}) exceeds "
                f"max_len ({self.max_len})")
        self.resident[rid] = _RState(
            req, hlen, replay[0], req.max_new,
            forced=collections.deque(replay[1:]))
        self._seen_rids.add(rid)
        self.parked.append(rid)
        self._c_resumes.inc()
        self._c_replayed.inc(len(replay))
        self._g_parked_sessions.set(len(self._parked_sessions))
        self._touch(rid)
        if self.obs.tracer is not None:
            self.obs.tracer.instant("session_resume", rid=rid,
                                    cached_len=hlen, replay=len(replay))
        self.peak_resident_tokens = max(self.peak_resident_tokens,
                                        self.resident_tokens())

    def release_session(self, rid: int):
        """Drop a parked session for good: free every page it holds."""
        self._parked_sessions.pop(rid)
        self._session_history.pop(rid, None)
        freed = self.pool.free_request(rid)
        if self.has_state:
            freed += self.pool.free_request(self._state_rid(rid))
        for pid in freed:
            self.store.release(pid)
        self.policy.forget_pages(freed)
        self._g_parked_sessions.set(len(self._parked_sessions))

    def preempt_lane(self, rid: int) -> bool:
        """Demote ``rid`` out of its lane back to the parked deque (the
        SLO scheduler's preempt-by-demotion).  Safe mid-flight: the
        in-flight tick's harvest checks residency, not lane state."""
        for i, r in enumerate(self.lanes):
            if r == rid:
                self._vacate(i)
                self.parked.appendleft(rid)
                self._c_preempt.inc()
                if self.obs.tracer is not None:
                    self.obs.tracer.instant("preempt", rid=rid,
                                            lane=i, by="scheduler")
                return True
        return False

    def sync(self):
        """Block until every dispatched tick/prefill/mover has executed
        (benchmark window boundaries)."""
        self.store.flush_movers()
        if self._inflight is not None:
            jax.block_until_ready(self._inflight[0])
        jax.block_until_ready(self._tokens_dev)
        jax.block_until_ready(self.store.pools)

    def run(self, max_ticks: int = 10_000):
        """Drive ticks until done.  If the loop ends with ``self.queue``
        non-empty, those requests are structurally inadmissible under the
        configured budgets (prompt needs more hot pages than the tier can
        ever free) -- they are left queued for the caller to inspect."""
        ticks = 0
        while (self.queue or self.resident or self._inflight is not None
               or self._pending_first) and ticks < max_ticks:
            if not self.step():
                break
            ticks += 1
        return self.finished

    # -- introspection -------------------------------------------------------

    def stats(self) -> dict:
        """Counter/gauge view of the engine (pool/store/policy sections
        are themselves registry views since the telemetry spine; the flat
        ``dispatch_p*``/``exec_p*`` keys are the honestly-labeled tick
        latency channels, DESIGN.md 13)."""
        gv = self.obs.metrics.get_value
        s = {"tick": self.tick_no,
             "backend": self.backend,
             "queued": len(self.queue),
             "parked": len(self.parked),
             "parked_sessions": len(self._parked_sessions),
             "session_parks": gv("engine_session_parks_total") or 0,
             "session_resumes": gv("engine_session_resumes_total") or 0,
             "replayed_tokens": gv("engine_replayed_tokens_total") or 0,
             "degraded": 1 if self._degraded else 0,
             "watchdog_trips": ((gv("engine_watchdog_trips_total",
                                    reason="latency") or 0)
                                + (gv("engine_watchdog_trips_total",
                                      reason="harvest_timeout") or 0)),
             "quarantines": ((gv("engine_quarantines_total",
                                 reason="checksum") or 0)
                             + (gv("engine_quarantines_total",
                                   reason="nan") or 0)),
             "resident_tokens": self.resident_tokens(),
             "peak_resident_tokens": self.peak_resident_tokens,
             "tokens_generated": self.tokens_generated,
             "preemptions": gv("engine_preemptions_total") or 0,
             "admissions": gv("engine_admissions_total") or 0,
             "prefill_compiles": self.prefill_compiles(),
             "hbm_bytes_used": self.store.hbm_bytes_used(),
             "cold_bytes": self.store.cold_bytes,
             "tiers": self.store.tier_counts(),
             "state_slots": {"hot": self.store.hot_state,
                             "warm": self.store.warm_state},
             "pool": dataclasses.asdict(self.pool.stats),
             "store": dict(self.store.stats),
             "policy": dict(self.policy.stats),
             "trigger": (dataclasses.asdict(self.policy.decision)
                         if self.policy.decision else None),
             "prefix": (dict(self.prefix.stats(),
                             prefill_skips=gv("engine_prefill_skips_total")
                             or 0,
                             skipped_tokens=gv(
                                 "engine_prefill_skipped_tokens_total") or 0,
                             shared_pages=gv(
                                 "engine_prefix_shared_pages_total") or 0)
                        if self.prefix is not None else None)}
        if self.obs.probe is not None:
            s.update(self.obs.probe.percentiles())
        return s
