"""Typed assist tasks -- the generalized Assist Warp subroutine model.

The paper presents CABA as a *framework*: one trigger/throttle/priority
mechanism (the AWC) dispatching many kinds of assist work -- data
compression (paper 5), memoization (8.1), prefetching (8.2).  This module
is that generalization for the TPU port.  Every assist capability is an
``AssistTask`` with a ``kind``:

  compress   trade idle compute for bandwidth (paper 5): a scheme pair
             (compress_fn, decompress_fn) with its cost traits
  memoize    trade storage for compute (paper 8.1): an LUT-backed
             function wrapper (see assist/memoize.py: ``Memoizer``)
  prefetch   hide transfer latency in idle cycles (paper 8.2): the
             cold-page promotion queue of the tiered KV cache

Tasks share one planning vocabulary: a ``SiteDescriptor`` (where the task
would run and what it moves/saves), ``RooflineTerms`` (the modeled step),
and an ``AssistDecision`` (the controller's verdict).  The
``AssistController`` (assist/controller.py) owns the trigger, throttle and
priority rules for all kinds; ``task.plan(site, roofline)`` is the
per-task entry into it.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Protocol, runtime_checkable

from repro.obs.metrics import MetricsRegistry
from repro.roofline.peaks import MODEL_TARGET

# the modeled chip's published peaks (roofline/peaks.py)
PEAK_FLOPS = MODEL_TARGET.bf16_flops
HBM_BW = MODEL_TARGET.hbm_bw
ICI_BW = MODEL_TARGET.ici_link_bw
# assumed, not published: host<->HBM DMA (PCIe-class; prefetch transfers)
HOST_BW = 16e9
VPU_OPS = 4 * 8 * 128 * 940e6  # ~3.9e12 elementwise lanes/s (8x128x4 @ 940MHz)

MIN_RATIO = 1.2           # paper 6: applications with >=10% compressibility;
                          # we require 20% to clear metadata overheads

KINDS = ("compress", "memoize", "prefetch")


@dataclasses.dataclass(frozen=True)
class RooflineTerms:
    """Per-device seconds for one step (from roofline/analysis.py)."""
    compute: float
    memory: float
    collective: float

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute, "memory": self.memory,
                 "collective": self.collective}
        return max(terms, key=terms.get)

    @property
    def step_time(self) -> float:
        # perfect-overlap lower bound: the dominant term
        return max(self.compute, self.memory, self.collective)


@dataclasses.dataclass(frozen=True)
class SiteDescriptor:
    """One assist opportunity in a step function.

    ``term`` names the roofline term the task relieves (memory |
    collective for compress, compute for memoize); ``bytes_per_step`` is
    what the site moves per step (for prefetch: per page).
    ``measured_ratio`` carries the site's measured compressibility (or an
    expected hit rate, for memoize sites) into ``task.plan``;
    ``flops_per_step`` is the recomputation a memoize hit would skip.
    """
    name: str                  # e.g. "weights", "kv", "grads"
    bytes_per_step: float      # uncompressed bytes this site moves per step
    term: str                  # relieved roofline term: memory|collective|compute
    lossless_required: bool    # grads/kv tolerate lossy; weights in-jit don't
    measured_ratio: float = 1.0
    flops_per_step: float = 0.0


@dataclasses.dataclass(frozen=True)
class AssistDecision:
    """The controller's verdict for one (task, site) pair."""
    site: str
    enabled: bool
    scheme: str
    ratio: float
    reason: str
    kind: str = "compress"
    budget: int = 0            # prefetch: pages the throttle allows per tick


# Deprecated name (pre-assist API): the compress-only decision record.
SiteDecision = AssistDecision


@runtime_checkable
class AssistTask(Protocol):
    """The assist-subroutine protocol every task kind implements."""
    kind: str
    name: str

    def plan(self, site: SiteDescriptor,
             roofline: Optional[RooflineTerms]) -> AssistDecision: ...

    def apply(self, *args, **kwargs): ...

    def stats(self) -> dict: ...


def _controller():
    # lazy: controller imports this module for the shared vocabulary
    from repro.assist.controller import AssistController
    return AssistController()


# ---------------------------------------------------------------------------
# compress (paper 5): scheme pair + traits.  One registered CompressTask is
# what the pre-assist API called an AssistSubroutine (AWS slot, Figure 5).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class CompressTask:
    """One registered compression scheme (paper: one AWS subroutine slot)."""
    sr_id: int
    name: str
    compress: Callable[..., Any]
    decompress: Callable[[Any], Any]
    lossless: bool
    jit_compress: bool        # usable inside jit (fixed-rate)?
    decomp_ops_per_byte: float

    kind = "compress"

    def plan(self, site: SiteDescriptor,
             roofline: Optional[RooflineTerms]) -> AssistDecision:
        if roofline is None:
            return AssistDecision(site.name, True, self.name,
                                  site.measured_ratio,
                                  "no roofline given: trigger bypassed",
                                  kind="compress")
        return _controller().decide(roofline, site, site.measured_ratio, self)

    def apply(self, x, *a, **kw):
        return self.compress(x, *a, **kw)

    def stats(self) -> dict:
        return {"kind": self.kind, "name": self.name,
                "lossless": self.lossless,
                "decomp_ops_per_byte": self.decomp_ops_per_byte}


# Deprecated name (pre-assist API).
AssistSubroutine = CompressTask


# ---------------------------------------------------------------------------
# prefetch (paper 8.2): the cold-page promotion queue.  WaSP-style lookahead
# moved out of cache/policy.py so serving, and any later consumer, share one
# trigger/throttle implementation.
# ---------------------------------------------------------------------------

# the known prefetch consumers (the ``kind=`` label vocabulary): lane
# lookahead, prefix-store re-promotion, session resume
PREFETCH_KINDS = ("lookahead", "prefix", "session")


class PrefetchTask:
    """Cold->warm page prefetch queue (the WaSP lookahead, paper 8.2).

    ``schedule`` enqueues the cold pages of a soon-to-run request;
    ``apply`` drains up to the throttled page budget, promoting through
    the provided store; ``account_swap_in`` scores the outcome.

    Accounting (the WaSP accuracy/timeliness taxonomy, DESIGN.md 13):
    every ISSUED page (entered the queue) resolves to exactly one of

      hit     promoted ahead of the swap-in that needed it
      late    needed while still cold (blocking promotion) or resident
              via some other path -- prefetch didn't deliver in time
      wasted  promoted (or queued) but freed / demoted back to cold
              before any swap-in used it

    via the ``_outstanding`` set, so ``issued == hit + late + wasted``
    holds exactly once the set drains (tests/test_obs.py).  The legacy
    ``counters`` dict is now a VIEW over the registry; its
    ``prefetch_misses`` keeps the old, broader meaning -- every cold page
    at swap-in, issued or not.
    """

    kind = "prefetch"

    def __init__(self, name: str = "coldpage", *, pages_per_tick: int = 2,
                 async_promote: bool = True, metrics=None,
                 controller=None):
        self.name = name
        self.pages_per_tick = pages_per_tick
        self.async_promote = async_promote
        # the consumer's controller (CachePolicy threads its own in) so
        # accept/reject decisions land in ITS registry; None falls back
        # to a fresh default controller per plan() call
        self.controller = controller
        self._queue: list[int] = []         # page ids queued cold->warm
        self._prefetched: set[int] = set()  # promoted ahead of swap-in
        self._outstanding: set[int] = set() # issued, outcome not yet known
        self.metrics = metrics if metrics is not None else MetricsRegistry()
        self._c = {o: self.metrics.counter(
            "prefetch_pages_total",
            "prefetch pages by outcome (issued == hit + late + wasted "
            "once outstanding drains)", outcome=o)
            for o in ("issued", "hit", "late", "wasted")}
        self._c_cold_miss = self.metrics.counter(
            "prefetch_cold_misses_total",
            "cold pages at swap-in (legacy miss: issued or not)")
        self._g_queue = self.metrics.gauge(
            "prefetch_queue_depth", "pages queued for cold->warm promotion")
        # per-consumer issue counters: the queue serves several producers
        # (lane lookahead, prefix-store re-promotion, session resume) and
        # the kind label keeps their traffic separable without touching
        # the outcome-conservation family above.  The known kinds are
        # PRE-BOUND (metrics discipline, DESIGN.md 16: no registry access
        # in tick scope); an out-of-vocabulary kind binds lazily, once.
        self._c_kind: dict = {
            kind: self.metrics.counter(
                "prefetch_issued_total",
                "pages entering the prefetch queue, by consumer kind",
                kind=kind)
            for kind in PREFETCH_KINDS}

    def _issued_kind(self, kind: str):
        c = self._c_kind.get(kind)
        if c is None:
            # lint-ok(metrics-bind): out-of-vocabulary kind, binds once
            c = self._c_kind[kind] = self.metrics.counter(
                "prefetch_issued_total",
                "pages entering the prefetch queue, by consumer kind",
                kind=kind)
        return c

    @property
    def counters(self) -> dict:
        """Legacy counter view (pre-registry key names and semantics)."""
        gv = self.metrics.get_value
        return {
            "prefetch_issued": gv("prefetch_pages_total",
                                  outcome="issued") or 0,
            "prefetch_hits": gv("prefetch_pages_total", outcome="hit") or 0,
            "prefetch_misses": gv("prefetch_cold_misses_total") or 0,
            "prefetch_late": gv("prefetch_pages_total", outcome="late") or 0,
            "prefetch_wasted": gv("prefetch_pages_total",
                                  outcome="wasted") or 0,
            "prefetch_outstanding": len(self._outstanding),
        }

    def build(self, **overrides) -> "PrefetchTask":
        """Fresh queue instance (the registry holds a prototype)."""
        kw = dict(pages_per_tick=self.pages_per_tick,
                  async_promote=self.async_promote,
                  controller=self.controller)
        kw.update(overrides)
        return PrefetchTask(self.name, **kw)

    # -- planning (trigger + throttle, via the controller) -------------------

    def plan(self, site: SiteDescriptor,
             roofline: Optional[RooflineTerms]) -> AssistDecision:
        ctl = self.controller if self.controller is not None \
            else _controller()
        return ctl.decide_prefetch(
            roofline, site, queued=len(self._queue),
            max_pages=self.pages_per_tick)

    # -- queue mechanics ------------------------------------------------------

    def schedule(self, page_ids, kind: str = "lookahead"):
        """Queue cold pages of a soon-to-run request for async promotion.

        ``kind`` names the producer ("lookahead" for the engine's closing-
        lane WaSP scan, "prefix" for matched radix pages at admission,
        "session" for a parked conversation's pre-turn re-promotion) and
        lands on ``prefetch_issued_total{kind=}``."""
        c_kind = self._issued_kind(kind)
        for p in page_ids:
            if p not in self._queue and p not in self._outstanding:
                self._queue.append(p)
                self._c["issued"].inc()
                c_kind.inc()
                self._outstanding.add(p)
        self._g_queue.set(len(self._queue))

    def apply(self, store, protected, make_warm_room, *,
              is_cold, budget: Optional[int] = None):
        """Drain up to ``budget`` queued pages through the store.

        ``make_warm_room(protected, cls)`` frees a warm slot of the page's
        class (policy-owned) -- the queue can carry token pages and parked
        state slabs, which promote into disjoint warm slot spaces;
        ``is_cold(pid)`` reports residency so stale entries are dropped.
        """
        if budget is None:
            budget = self.pages_per_tick
        try:
            while budget > 0 and self._queue:
                pid = self._queue[0]
                if not is_cold(pid):              # already resident / freed
                    self._queue.pop(0)
                    continue
                cls = store.cls_of(pid)
                if store.n_free_warm_cls(cls) == 0 \
                        and not make_warm_room(protected, cls):
                    return
                self._queue.pop(0)
                store.promote_to_warm(pid, async_=self.async_promote)
                self._prefetched.add(pid)
                budget -= 1
        finally:
            self._g_queue.set(len(self._queue))

    def account_swap_in(self, page_ids, cold_page_ids):
        """Called ONCE per successful swap-in of a parked request:
        ``cold_page_ids`` (still cold when scheduling started) needed a
        blocking promotion (legacy miss); pages the queue promoted ahead
        of time are hits (the WaSP payoff).  Issued pages the prefetch
        did not deliver resolve as LATE."""
        cold = set(cold_page_ids)
        self._c_cold_miss.inc(len(cold))
        for p in page_ids:
            if p not in cold and p in self._prefetched:
                self._c["hit"].inc()
                self._prefetched.discard(p)
                self._outstanding.discard(p)
            elif p in self._outstanding:
                # still cold (blocking promotion) or resident via another
                # path: either way the prefetch was too late
                self._c["late"].inc()
                self._outstanding.discard(p)
                if p in self._queue:
                    self._queue.remove(p)
        self._g_queue.set(len(self._queue))

    def forget_pages(self, page_ids):
        """Drop freed pages so recycled page ids can never be miscounted
        as hits for a different request.  Issued pages freed unused
        resolve as WASTED."""
        for p in page_ids:
            self._prefetched.discard(p)
            if p in self._queue:
                self._queue.remove(p)
            if p in self._outstanding:
                self._c["wasted"].inc()
                self._outstanding.discard(p)
        self._g_queue.set(len(self._queue))

    def discard_prefetched(self, pid):
        """A page demoted back to cold is no longer a usable prefetch:
        the promotion work resolves as WASTED (still-queued pages stay
        outstanding -- they may yet promote and hit)."""
        if pid in self._prefetched:
            self._prefetched.discard(pid)
            self._outstanding.discard(pid)
            self._c["wasted"].inc()

    def stats(self) -> dict:
        return {"kind": self.kind, "name": self.name,
                "queued": len(self._queue), **self.counters}
