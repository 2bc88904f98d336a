"""Bounded LRU cache of compiled executors over shape classes.

One jit'd executor per (kind, shape-class, feature widths, backend,
dispatch knobs); every graph padded into the same class reuses the
executor — and therefore its trace and XLA executable — with zero
recompilation. Batched variants vmap the same forward over a stacked
class group for `Engine.serve_batch`.

The cache is LRU-bounded (``max_entries``) so long-lived multi-tenant
servers can't grow it without limit: the least-recently-used executor is
dropped (and garbage-collects its XLA executable) when a new build would
exceed the bound. Per-shape-class hit/miss/eviction counters feed
``Engine.stats()`` telemetry.

Request staging rides in the same cache, in a table of its own with its
own counters and the same bound: one jit'd function per (shape class,
graph size, width) that permutes + zero-pads a request's features onto
the class's input rows, and one that slices + un-permutes the output.
Their inputs are per-graph shapes, so each graph size compiles its own
pair; keeping them here bounds that growth, drops them with their class
(``invalidate_class``), and counts their builds in ``builds``, the
cold-detect delta the latency EWMAs read.

The closed-over PartitionMeta comes from ``ShapeClass.to_meta()`` only,
never from a member graph, so per-graph facts can't split a class.
Padded partitions arrive as device arrays (Engine.register places them),
so executor calls pay no host-to-device transfer for the graph itself.
"""
from __future__ import annotations

import collections
import threading

import jax
import jax.numpy as jnp

from repro.core.hybrid_spmm import gcn_forward, hybrid_spmm
from repro.obs.metrics import Counter, MetricsRegistry
from repro.obs.trace import NULL_TRACER
from repro.serving.chaos import NULL_INJECTOR, InjectedFault

from .shape_class import ShapeClass


class CacheStats:
    """Executor-cache telemetry on `repro.obs.metrics` counters.

    One `Counter` per field — the unified metrics backing store — while
    the legacy integer attribute surface (``stats.hits`` etc.) survives
    as read-only properties, so external readers (tests, benchmark
    prints) are
    unchanged. Mutation goes through the ``inc_*`` methods; multi-field
    coherence still comes from the owning ``ExecutorCache._lock`` — a
    counter's own lock only makes its single value race-free.
    """

    def __init__(self, prefix: str = "cache", registry=None):
        self._hits = Counter(prefix + ".hits", registry)
        self._misses = Counter(prefix + ".misses", registry)
        self._evictions = Counter(prefix + ".evictions", registry)
        self._invalidations = Counter(prefix + ".invalidations", registry)

    def inc_hits(self, n: int = 1) -> None:
        self._hits.inc(n)

    def inc_misses(self, n: int = 1) -> None:
        self._misses.inc(n)

    def inc_evictions(self, n: int = 1) -> None:
        self._evictions.inc(n)

    def inc_invalidations(self, n: int = 1) -> None:
        self._invalidations.inc(n)

    @property
    def hits(self) -> int:
        return self._hits.value

    @property
    def misses(self) -> int:
        return self._misses.value

    @property
    def evictions(self) -> int:
        return self._evictions.value

    @property
    def invalidations(self) -> int:
        return self._invalidations.value

    @property
    def total(self) -> int:
        return self.hits + self.misses

    def as_dict(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "evictions": self.evictions,
                "invalidations": self.invalidations}


def _stage_fn(rows: int):
    def _stage_x(x, perm):
        """Request features in the class's input layout: rows gathered
        into the graph's reorder (``perm`` None: input order), then zero
        rows up to ``rows``. Exact: no arithmetic touches a value."""
        if perm is not None:
            x = x[perm]
        return jnp.pad(x, ((0, rows - x.shape[0]), (0, 0)))
    return jax.jit(_stage_x)


def _unstage_fn(n_rows: int):
    def _unstage_y(y, inv_perm):
        """Executor output back in the caller's vertex order: the
        class's padding rows sliced off, then un-permuted (``inv_perm``
        None: the graph was registered in input order)."""
        y = y[:n_rows]
        return y if inv_perm is None else y[inv_perm]
    return jax.jit(_unstage_y)


class ExecutorCache:
    """jit'd executors keyed by (kind, shape class, widths, backend...).

    Every key's second element is the ShapeClass, which is how the
    per-class telemetry attributes hits/misses/evictions.
    """

    def __init__(self, backend: str = "xla", block_cols: int = 0,
                 ell_dispatch: str = "ragged", max_entries: int = 128):
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.backend = backend
        self.block_cols = block_cols
        self.ell_dispatch = ell_dispatch
        self.max_entries = max_entries
        self._fns: collections.OrderedDict = collections.OrderedDict()
        # Unified metrics backing store: the global cache counters live
        # in this registry (`stats_snapshot` re-exports them); per-class
        # CacheStats stay registry-less (their names would collide).
        self.metrics = MetricsRegistry()
        self.stats = CacheStats("cache", self.metrics)
        self._class_stats: dict = {}   # ShapeClass -> CacheStats
        # Request staging functions (`stage` / `unstage`): a table and
        # counters of their own, so they neither evict executors nor
        # move the executor counters.
        self._staging: collections.OrderedDict = collections.OrderedDict()
        self.staging = CacheStats("cache.staging", self.metrics)
        # Observability hooks (repro.obs): cache.hit/cache.miss instant
        # events. Off by default; `Engine.attach_tracer` swaps it in.
        self.tracer = NULL_TRACER
        # Chaos hook (repro.serving.chaos): the "compile" injection site
        # lives in the `_get` miss path. `Engine.attach_injector` swaps
        # a live injector in; NULL_INJECTOR keeps the path zero-cost.
        self.injector = NULL_INJECTOR
        # Autotuned ragged-kernel configs, ShapeClass -> sorted item
        # tuple. Part of every executor key, so applying a new winner
        # can never alias a stale compiled executor.
        self._tuned: dict = {}
        # Guards _fns/_class_stats bookkeeping: the pipelined dispatch
        # path looks executors up from staging workers concurrently with
        # user-thread infer()/spmm() calls. build() (trace + compile)
        # runs INSIDE the lock so one cold key compiles once, not once
        # per racing thread — concurrent lookups of other, warm keys
        # briefly queue behind it, which is the price of a coherent
        # miss counter (the frontend's cold-sample detector).
        self._lock = threading.RLock()

    def _per_class(self, sc: ShapeClass) -> CacheStats:
        st = self._class_stats.get(sc)
        if st is None:
            st = self._class_stats[sc] = CacheStats("cache.class")
        return st

    def _get(self, key, build):
        tr = self.tracer
        with self._lock:
            sc = key[1]
            cls = self._per_class(sc)
            fn = self._fns.get(key)
            if fn is None:
                self.stats.inc_misses()
                cls.inc_misses()
                if tr.enabled:
                    tr.instant("cache.miss", "engine",
                               args={"kind": key[0]})
                inj = self.injector
                if inj.enabled and inj.poll("compile") is not None:
                    # injected compile failure: the build never ran, so
                    # the next lookup misses again and a retry recompiles
                    # (transient by construction)
                    raise InjectedFault(
                        "compile", detail=f"executor build for {key[0]}")
                fn = build()
                self._fns[key] = fn
                while len(self._fns) > self.max_entries:
                    old_key, _ = self._fns.popitem(last=False)   # LRU out
                    self.stats.inc_evictions()
                    self._per_class(old_key[1]).inc_evictions()
            else:
                self._fns.move_to_end(key)                       # mark MRU
                self.stats.inc_hits()
                cls.inc_hits()
                if tr.enabled:
                    tr.instant("cache.hit", "engine",
                               args={"kind": key[0]})
            return fn

    def _get_staging(self, key, build):
        with self._lock:
            fn = self._staging.get(key)
            if fn is None:
                self.staging.inc_misses()
                fn = self._staging[key] = build()
                while len(self._staging) > self.max_entries:
                    self._staging.popitem(last=False)            # LRU out
                    self.staging.inc_evictions()
            else:
                self._staging.move_to_end(key)                   # mark MRU
                self.staging.inc_hits()
            return fn

    @property
    def builds(self) -> int:
        """Executors and staging functions this cache has built (traced
        + compiled). A dispatch that raised it was cold."""
        return self.stats.misses + self.staging.misses

    def __len__(self) -> int:
        with self._lock:
            return len(self._fns)

    @property
    def size(self) -> int:
        """Number of live compiled executors (public; callers must not
        reach into ``_fns``)."""
        with self._lock:
            return len(self._fns)

    def stats_snapshot(self) -> dict:
        """Coherent copy of the global hit/miss/evict counters. Public
        readers use this instead of ``.stats`` fields: staging workers
        mutate the counters under ``_lock``, so an unguarded multi-field
        read could pair a pre-update ``hits`` with a post-update
        ``misses``."""
        with self._lock:
            return self.stats.as_dict()

    def staging_snapshot(self) -> dict:
        """Coherent copy of the staging counters and live entries."""
        with self._lock:
            return {**self.staging.as_dict(), "entries": len(self._staging)}

    def class_stats(self) -> dict:
        """Per-shape-class telemetry: {summary str: hit/miss/evict dict}."""
        with self._lock:
            return {sc.summary(): st.as_dict()
                    for sc, st in self._class_stats.items()}

    def traffic_by_class(self) -> dict:
        """Cumulative executor lookups (hits + misses) per ShapeClass.

        The lifecycle manager's traffic gate reads this: a class with no
        lookups in a window runs no kernels, so retiring it buys nothing
        and would only spend recompile budget.
        """
        with self._lock:
            return {sc: st.total for sc, st in self._class_stats.items()}

    def invalidate_class(self, sc: ShapeClass) -> int:
        """Drop every cached executor and staging function keyed on
        ``sc`` (class retired).

        Distinct from LRU eviction — invalidations are counted
        separately (globally and per class) so capacity pressure and
        lifecycle churn stay distinguishable in telemetry. The LRU
        order of surviving entries is untouched. Returns the number of
        executors dropped; staging functions count in ``staging``.
        """
        with self._lock:
            dead = [key for key in self._fns if key[1] == sc]
            for key in dead:
                del self._fns[key]
            if dead:
                self.stats.inc_invalidations(len(dead))
                self._per_class(sc).inc_invalidations(len(dead))
            staged = [key for key in self._staging if key[1] == sc]
            for key in staged:
                del self._staging[key]
            if staged:
                self.staging.inc_invalidations(len(staged))
            return len(dead)

    # -------------------------------------------------------- autotune -----
    def set_tuned(self, sc: ShapeClass, cfg: dict) -> int:
        """Apply an autotuned ragged-kernel config to every executor of
        class ``sc`` (`repro.kernels.autotune` winners land here).

        The config rides in every executor key, so stale compiled
        executors for the class are invalidated and the next lookup
        rebuilds with ``ell_tune`` threaded down the dispatch path.
        Tuned and default outputs are bitwise-equal by kernel
        construction. Returns the number of executors invalidated; a
        no-op (same config already applied, or empty config on an
        untuned class) invalidates nothing.
        """
        with self._lock:
            t = tuple(sorted(cfg.items()))
            if self._tuned.get(sc, ()) == t:
                return 0
            if t:
                self._tuned[sc] = t
            else:
                self._tuned.pop(sc, None)
            return self.invalidate_class(sc)

    def tuned_for(self, sc: ShapeClass) -> dict:
        """The applied tuned config for ``sc`` ({} = defaults)."""
        with self._lock:
            return dict(self._tuned.get(sc, ()))

    def _tune_of(self, sc):
        return self._tuned.get(sc, ())

    # ------------------------------------------------------------ spmm -----
    def spmm(self, sc: ShapeClass, f: int):
        """Executor for Y = A @ B over a padded partition of class sc.

        Signature: fn(part, b[n_cols_padded, f]) -> y[n_rows_padded, f].
        """
        with self._lock:
            tune = self._tune_of(sc)
            key = ("spmm", sc, f, self.backend, self.ell_dispatch, tune)

            def build():
                meta = sc.to_meta()
                backend, dispatch = self.backend, self.ell_dispatch
                ell_tune = dict(tune) or None

                @jax.jit
                def fn(part, b):
                    return hybrid_spmm(part, b, meta=meta, backend=backend,
                                       ell_dispatch=dispatch,
                                       ell_tune=ell_tune)
                return fn
            return self._get(key, build)

    # --------------------------------------------------------- staging -----
    def stage(self, sc: ShapeClass, n_cols: int, f: int, permuted: bool):
        """Staging of one graph size's request features onto class sc.

        Signature: fn(x[n_cols, f], perm or None) ->
        x[n_col_tiles * tile, f], compiled as module ``jit__stage_x``.
        """
        return self._get_staging(
            ("stage", sc, n_cols, f, permuted),
            lambda: _stage_fn(sc.n_col_tiles * sc.tile))

    def unstage(self, sc: ShapeClass, n_rows: int, f: int, permuted: bool):
        """Unstaging of class sc's output for a graph of ``n_rows``.

        Signature: fn(y[n_row_tiles * tile, f], inv_perm or None) ->
        y[n_rows, f], compiled as module ``jit__unstage_y``.
        """
        return self._get_staging(("unstage", sc, n_rows, f, permuted),
                                 lambda: _unstage_fn(n_rows))

    # ------------------------------------------------------------- gcn -----
    def _gcn_key(self, sc, f_in, w_shapes):
        return ("gcn", sc, f_in, w_shapes, self.backend, self.block_cols,
                self.ell_dispatch, self._tune_of(sc))

    def _gcn_build(self, sc):
        meta = sc.to_meta()
        backend = self.backend
        block_cols, dispatch = self.block_cols, self.ell_dispatch
        ell_tune = dict(self._tune_of(sc)) or None

        def fwd(part, x, weights):
            return gcn_forward(part, x, weights, meta=meta, backend=backend,
                               block_cols=block_cols, ell_dispatch=dispatch,
                               ell_tune=ell_tune)
        return fwd

    def gcn(self, sc: ShapeClass, f_in: int, w_shapes: tuple):
        """Executor for the 2+-layer GCN forward over one padded graph.

        Signature: fn(part, x[n_cols_padded, f_in], weights) ->
        logits[n_rows_padded, w_shapes[-1][-1]].
        """
        with self._lock:
            key = self._gcn_key(sc, f_in, w_shapes)
            return self._get(key, lambda: jax.jit(self._gcn_build(sc)))

    def gcn_batched(self, sc: ShapeClass, f_in: int, w_shapes: tuple,
                    batch: int):
        """vmapped GCN executor over a stacked class group of ``batch``
        graphs: every pytree arg gains a leading batch axis."""
        with self._lock:
            key = self._gcn_key(sc, f_in, w_shapes) + ("batch", batch)
            return self._get(
                key, lambda: jax.jit(jax.vmap(self._gcn_build(sc))))

    def summary(self) -> str:
        with self._lock:
            kinds: dict = {}
            for key in self._fns:
                kinds[key[0]] = kinds.get(key[0], 0) + 1
            return (f"ExecutorCache backend={self.backend} "
                    f"executors={len(self._fns)}/{self.max_entries} "
                    f"({kinds}) "
                    f"hits={self.stats.hits} misses={self.stats.misses} "
                    f"evictions={self.stats.evictions}")
