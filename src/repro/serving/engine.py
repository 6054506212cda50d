"""Batched serving engine with continuous batching.

A fixed pool of B slots over one decode-state pytree.  New requests are
prefillled individually (padded to a bucketed length, masked via
``true_len``) and spliced into free slots along the batch axis; one jitted
``decode_step`` advances every active slot per tick; finished slots are
recycled without stalling the rest of the batch -- continuous batching a
la Orca/vLLM, reduced to the single-controller JAX setting.

The decode loop is HOST-SYNC-FREE (DESIGN.md 12):

* sampling is fused into the jitted step (per-slot temperature vector and
  a threaded PRNG key are jit inputs; greedy/categorical select happens on
  device), so the host never materializes logits;
* the sampled tokens stay device-resident -- they are the NEXT tick's
  input without a round trip;
* retirement reads the *previous* tick's tokens (``jax.device_get`` of a
  one-tick-lagged handle) while the current tick executes, so the host
  never blocks on the token it just dispatched.  EOS discovery therefore
  lags one tick: the slot decodes one junk token that is discarded at the
  next harvest; output streams are unchanged.
* prompt lengths are BUCKETED (models/model.py::prompt_bucket): prefill
  compiles once per power-of-two bucket instead of once per distinct
  prompt length.

The engine takes ``kv_mode`` straight through to the cache (CABA KV site):
int8 doubles the resident slot count for the same HBM.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.analysis.runtime import tick_guard
from repro.configs.base import DEFAULT_EOS_ID
from repro.models.model import ModelFns, prompt_bucket
from repro.obs import Observability
from repro.obs.metrics import TOKENS_BUCKETS


def stage_host(mirror: np.ndarray) -> jax.Array:
    """Device copy of a host mirror the engine keeps mutating.

    ``jnp.asarray`` may alias an aligned numpy buffer without copying (the
    CPU backend does), and the dispatched tick reads it asynchronously: an
    in-place host update right after dispatch would then race the tick.
    Staging a private copy makes the tick's input immutable."""
    return jnp.asarray(mirror.copy())


@dataclasses.dataclass
class Request:
    rid: int
    prompt: list            # token ids
    max_new: int = 16
    temperature: float = 0.0
    out: list = dataclasses.field(default_factory=list)
    done: bool = False
    # SLO class name ("interactive"/"batch"; None = untagged best-effort)
    # -- drives bounded-queue shed ordering, lowest class sheds first
    cls: Optional[str] = None
    # terminal error status ("shed" / "checksum" / "nan" / ...); a request
    # retired with an error has no valid output stream
    error: Optional[str] = None


@dataclasses.dataclass
class _Slot:
    req: Optional[Request] = None
    remaining: int = 0


class EngineBase:
    """Request intake + sampling shared by the dense and paged engines.

    Subclasses provide ``self.queue`` / ``self.rng`` and call
    ``_init_intake()`` from their constructor.  ``from_config`` is the
    unified construction path: one ``ServeConfig`` (with its nested
    ``AssistSpec``) builds either engine, so callers never touch the
    divergent constructor signatures directly.
    """

    #: prompt-length bucket quantum of the dense engine (the paged engine
    #: buckets on its page size instead)
    PREFILL_QUANTUM = 16

    @classmethod
    def from_config(cls, scfg, model, params, obs=None) -> "EngineBase":
        """Build the engine a ServeConfig describes (dense or paged).

        ``obs`` overrides the Observability bundle (launch/serve.py passes
        one bound to the process-global registry for /metrics export);
        by default the engine gets a private bundle built from
        ``scfg.obs``."""
        spec = scfg.assist
        if obs is None:
            obs = Observability(getattr(scfg, "obs", None))
        if spec.paged:
            from repro.serving.paged_engine import PagedEngine
            return PagedEngine(
                model, params, lanes=scfg.slots, max_len=scfg.max_len,
                tier=scfg.tier_config(), eos_id=scfg.eos_id,
                seed=scfg.seed, backend=spec.attn_backend,
                use_roofline_trigger=spec.use_roofline_trigger,
                max_cold_pages=spec.max_cold_pages,
                prefix_reuse=spec.prefix_reuse,
                prefix_max_nodes=spec.prefix_max_nodes,
                prefix_min_pages=spec.prefix_min_pages,
                prefix_prefetch=spec.prefix_prefetch,
                max_queue=getattr(scfg, "max_queue", None),
                fault=getattr(scfg, "fault", None),
                harvest_timeout_s=getattr(scfg, "harvest_timeout_s", None),
                obs=obs)
        return Engine(model, params, batch_slots=scfg.slots,
                      max_len=scfg.max_len, kv_mode=spec.kv,
                      eos_id=scfg.eos_id, seed=scfg.seed,
                      max_queue=getattr(scfg, "max_queue", None), obs=obs)

    #: shed ranking for the bounded admission queue: HIGHER rank sheds
    #: first.  Mirrors the default SLO classes (sessions/spec.py) without
    #: importing them; unknown class names shed before any known class,
    #: untagged requests before those, interactive always last.
    _SHED_RANK = {"interactive": 0, "batch": 1}

    def _init_intake(self, metrics=None, max_queue: Optional[int] = None):
        from repro.obs.metrics import NULL_REGISTRY
        self._seen_rids: set[int] = set()
        self._next_rid = 0
        self.max_queue = max_queue
        m = metrics if metrics is not None else NULL_REGISTRY
        self._g_qdepth = m.gauge(
            "engine_queue_depth", "requests waiting for admission "
            "(bounded when max_queue is set)")
        self._c_rejected = {r: m.counter(
            "engine_admission_rejected_total",
            "submissions rejected at intake", reason=r)
            for r in ("shed", "oversize")}

    def _shed_rank(self, req: Request) -> int:
        cls = getattr(req, "cls", None)
        if cls is None:
            return 1 << 30
        return self._SHED_RANK.get(cls, 1 << 20)

    def _reject(self, req: Request, reason: str):
        req.error = reason
        req.done = True
        self.finished.append(req)
        self._c_rejected[reason].inc()

    def submit(self, req: Request):
        if req.rid in self._seen_rids:      # recycle colliding rids
            req.rid = self._next_rid
        self._seen_rids.add(req.rid)
        self._next_rid = max(self._next_rid, req.rid + 1)
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            # SLO-class-aware shed: drop the least-important request among
            # the queue plus the newcomer (ties shed the newcomer, keeping
            # FIFO fairness for already-accepted work) -- interactive
            # sheds last by construction of the rank order
            victim, worst = req, self._shed_rank(req)
            for cand in self.queue:
                r = self._shed_rank(cand)
                if r > worst:
                    victim, worst = cand, r
            self._reject(victim, "shed")
            if victim is req:
                self._g_qdepth.set(len(self.queue))
                return
            self.queue.remove(victim)
        self.queue.append(req)
        self._g_qdepth.set(len(self.queue))

    #: fold_in tags separating the two in-jit sampling streams -- decode
    #: keys fold (rng, DECODE_STREAM, tick) and prefill (rng,
    #: PREFILL_STREAM, rid), so a tick number colliding with a request id
    #: can never key two categorical draws identically
    DECODE_STREAM = 0
    PREFILL_STREAM = 1

    @staticmethod
    def _select_token(logits, temps, key):
        """On-device greedy/categorical select (the fused sampling site).

        logits: f32[B, V]; temps: f32[B] (<= 0 means greedy -- those rows
        never read the key, so greedy streams are key-independent).
        """
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        t = jnp.where(temps > 0.0, temps, 1.0)
        sampled = jax.random.categorical(
            key, logits / t[:, None], axis=-1).astype(jnp.int32)
        return jnp.where(temps > 0.0, sampled, greedy)

    def prefill_compiles(self) -> int:
        """Distinct prefill shapes compiled so far (the retrace gauge:
        analysis/runtime.py::assert_compile_bound checks it against the
        bucket count)."""
        return self._prefill._cache_size()

    def _pad_prompt(self, prompt, quantum: int) -> dict:
        """Bucket-padded prefill batch: tokens padded up to the bucket,
        true_len carrying the real length for the in-jit mask."""
        plen = len(prompt)
        bucket = prompt_bucket(plen, self.max_len, quantum) \
            if self.bucket_prefill else plen
        self._h_bucket.observe(bucket)
        toks = np.zeros((1, bucket), np.int32)
        toks[0, :plen] = prompt
        return {"tokens": jnp.asarray(toks),
                "true_len": jnp.asarray([plen], jnp.int32)}

class Engine(EngineBase):
    """Greedy/temperature sampling over a slot-batched decode state."""

    def __init__(self, model: ModelFns, params, *, batch_slots: int,
                 max_len: int, kv_mode: str = "bf16",
                 eos_id: int = DEFAULT_EOS_ID, seed: int = 0,
                 bucket_prefill: bool = True,
                 max_queue: Optional[int] = None,
                 obs: Optional[Observability] = None):
        self.model = model
        self.params = params
        self.B = batch_slots
        self.max_len = max_len
        self.kv_mode = kv_mode
        self.eos_id = eos_id
        self.bucket_prefill = bucket_prefill
        self.obs = obs if obs is not None else Observability()
        # strict mode wraps the jitted tick dispatch in a transfer guard
        # (DESIGN.md 16); OFF shares one no-op context -- fence-free
        self._strict_transfers = bool(self.obs.spec.strict_transfers)
        self._tick_guard = tick_guard(self._strict_transfers)
        m = self.obs.metrics
        self._c_tokens = m.counter("engine_tokens_generated_total",
                                   "decode tokens harvested")
        self._h_bucket = m.histogram(
            "engine_prefill_bucket_tokens",
            "padded prompt-bucket length per prefill", TOKENS_BUCKETS)
        self.slots = [_Slot() for _ in range(batch_slots)]
        self.state = model.init_state(batch_slots, max_len, kv_mode=kv_mode)
        self.tokens = jnp.zeros((batch_slots, 1), jnp.int32)
        self.rng = jax.random.PRNGKey(seed)
        self.queue: collections.deque[Request] = collections.deque()
        self.finished: list[Request] = []
        self._temps = np.zeros(batch_slots, np.float32)
        self._tick = 0
        # one-tick-lagged readback state: the just-dispatched tokens and
        # the (slot, req, remaining-after) snapshot they belong to
        self._inflight: Optional[tuple] = None
        self._pending_first: list = []      # [(req, first-token handle)]
        self._init_intake(metrics=m, max_queue=max_queue)

        def step_fn(params, state, tokens, temps, rng, tick):
            logits, state = model.decode_step(params, state, tokens)
            key = jax.random.fold_in(
                jax.random.fold_in(rng, self.DECODE_STREAM), tick)
            nxt = self._select_token(logits[:, 0], temps, key)
            return nxt, state

        self._decode = jax.jit(step_fn)

        def prefill_fn(params, batch, temp, rng, salt):
            logits, one_state = model.prefill(params, batch, max_len,
                                              moe_dropless=True,
                                              kv_mode=kv_mode)
            tl = batch["true_len"]
            last = jnp.take_along_axis(logits, (tl - 1)[:, None, None],
                                       axis=1)[:, 0]
            temps = jnp.broadcast_to(jnp.asarray(temp, jnp.float32),
                                     (last.shape[0],))
            key = jax.random.fold_in(
                jax.random.fold_in(rng, self.PREFILL_STREAM), salt)
            tok = self._select_token(last, temps, key)
            return tok, one_state

        self._prefill = jax.jit(prefill_fn)

        # plain caches are [B, ...]; scan-stacked caches are [n_scan, B, ...]
        def splice_tree(state, one_state, slot):
            def put(buf, new):
                if buf.shape == new.shape:         # B == 1: replace outright
                    return new.astype(buf.dtype)
                if buf.shape and buf.shape[0] == self.B and new.shape[0] == 1:
                    return buf.at[slot].set(new[0].astype(buf.dtype))
                if (buf.ndim >= 2 and buf.shape[1] == self.B
                        and new.shape[1] == 1):
                    return buf.at[:, slot].set(new[:, 0].astype(buf.dtype))
                return buf
            return jax.tree.map(put, state, one_state)

        self._splice = jax.jit(splice_tree, donate_argnums=(0,))

    # -- request lifecycle ---------------------------------------------------

    def _free_slot(self) -> Optional[int]:
        for i, s in enumerate(self.slots):
            if s.req is None:
                return i
        return None

    def _admit(self):
        while self.queue:
            slot = self._free_slot()
            if slot is None:
                return
            req = self.queue.popleft()
            batch = self._pad_prompt(req.prompt, self.PREFILL_QUANTUM)
            tok, one_state = self._prefill(self.params, batch,
                                           float(req.temperature), self.rng,
                                           req.rid)
            self.state = self._splice(self.state, one_state, slot)
            self.tokens = self.tokens.at[slot, 0].set(tok[0])
            self._temps[slot] = req.temperature
            # the first token is appended at the next harvest (no sync here)
            self._pending_first.append((req, tok))
            self.slots[slot] = _Slot(req, req.max_new - 1)

    # -- main loop -----------------------------------------------------------

    def step(self):
        """One engine tick: admit, decode all active slots (sampling
        fused), then harvest the PREVIOUS tick's tokens while this tick
        executes."""
        self._admit()
        active = [(i, s) for i, s in enumerate(self.slots)
                  if s.req is not None]
        if not active:
            prev, self._inflight = self._inflight, None
            return self._harvest(prev)
        self._tick += 1
        # stage host mirrors ABOVE the transfer guard; the tick counter is
        # staged only in strict mode (weak python int vs strong int32 hash
        # to different jit cache entries -- one compile per mode)
        temps = stage_host(self._temps)
        tick = (jnp.asarray(self._tick, jnp.int32)
                if self._strict_transfers else self._tick)
        probe = self.obs.probe
        t0 = time.perf_counter() if probe is not None else 0.0
        with self._tick_guard():
            nxt, self.state = self._decode(self.params, self.state,
                                           self.tokens, temps, self.rng,
                                           tick)
        if probe is not None:
            probe.record_dispatch(time.perf_counter() - t0)
            if probe.should_fence(self._tick):
                # execution-true sample: drain the device queue through
                # this tick (what a request actually waits)
                # sync-ok: every-Nth execution-true probe fence
                jax.block_until_ready(nxt)
                probe.record_exec(time.perf_counter() - t0)
        self.tokens = nxt[:, None]
        snapshot = []
        for i, s in active:
            s.remaining -= 1                     # host-known: speculative
            snapshot.append((i, s.req, s.remaining))
            if s.remaining <= 0:
                # out of budget: free the slot now (its final token is in
                # flight and lands at the next harvest, keyed by req)
                self.slots[i] = _Slot()
        prev, self._inflight = self._inflight, (nxt, snapshot)
        self._harvest(prev)
        return True

    def _harvest(self, prev) -> bool:
        """Land the lagged tokens: append, retire EOS/out-of-budget
        requests.  The device_get here overlaps the tick dispatched just
        before it."""
        firsts, self._pending_first = self._pending_first, []
        if prev is None and not firsts:
            return False
        handles = [t for _, t in firsts] + ([prev[0]] if prev else [])
        # sync-ok: lagged harvest -- device_get overlaps the in-flight tick
        vals = jax.device_get(handles)
        for (req, _), v in zip(firsts, vals):
            req.out.append(int(np.asarray(v).ravel()[0]))
        if prev is not None:
            nxt = np.asarray(vals[-1])
            for i, req, rem in prev[1]:
                if req.done:                    # junk token past EOS
                    continue
                tok = int(nxt[i])
                req.out.append(tok)
                self._c_tokens.inc()
                if rem <= 0 or tok == self.eos_id:
                    req.done = True
                    self.finished.append(req)
                    if self.slots[i].req is req:
                        self.slots[i] = _Slot()
        return True

    def sync(self):
        """Block until every dispatched tick/prefill has executed
        (benchmark window boundaries)."""
        if self._inflight is not None:
            jax.block_until_ready(self._inflight[0])
        jax.block_until_ready(self.tokens)

    def run(self, max_ticks: int = 10_000):
        ticks = 0
        while (self.queue or any(s.req for s in self.slots)
               or self._inflight is not None or self._pending_first) \
                and ticks < max_ticks:
            self.step()
            ticks += 1
        return self.finished

    def stats(self) -> dict:
        """Registry view of the dense engine's counters (the paged
        engine's richer ``stats()`` is the reference shape)."""
        gv = self.obs.metrics.get_value
        s = {"tick": self._tick,
             "queued": len(self.queue),
             "active_slots": sum(1 for sl in self.slots
                                 if sl.req is not None),
             "tokens_generated": gv("engine_tokens_generated_total") or 0}
        if self.obs.probe is not None:
            s.update(self.obs.probe.percentiles())
        return s
