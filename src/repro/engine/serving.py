"""The serving engine: offline registration, cached inference, batching.

Request path (mirrors the paper's offline/online split):

  offline  — ``register``: reorder, tri-partition (Algorithms 1+2), pad
             into a shape class. Done once per graph.
  online   — ``spmm`` / ``infer``: send the request features to the
             device, permute + pad them there, run the class's cached
             executor, slice + un-permute the output on the device.
           — ``serve_batch``: group requests by (shape class, widths),
             then ``serve_group`` stacks each group and runs one
             vmapped executor per group.

``serve_group`` is the single-group dispatch primitive shared by
``serve_batch`` (which forms groups from one call's requests) and the
standing `repro.serving.RequestQueue` (which forms groups from traffic
accumulated across calls and closes them on deadline pressure).
``serve_group_async`` is its non-blocking core: it performs all
host-side staging (pad, stack, executor lookup) and *enqueues* the
device work — JAX dispatch is asynchronous, so the returned arrays are
unresolved device values — plus a completion meta dict (``cold`` flag,
``complete``/``ready`` hooks) that the pipelined frontend's completion
drainer uses to overlap the next batch's staging with this batch's
device compute.

All padding/slicing happens outside the executors, so their traced
computation depends only on the shape class and feature widths. The
per-graph staging (permute, pad) and unstaging (slice, un-permute) are
jitted device functions of their own, held in the same `ExecutorCache`.
"""
from __future__ import annotations

import collections
import dataclasses
import threading
import time
from typing import Callable, NamedTuple, Optional

import numpy as np
import jax
import jax.numpy as jnp
import jax.tree_util as jtu

from repro.core.formats import CSRMatrix, PartitionMeta, TriPartition
from repro.core.gat import gat_param_shapes
from repro.core.partition import PartitionConfig, analyze_and_partition
from repro.core.reorder import reorder as reorder_csr
from repro.obs.metrics import Counter, MetricsRegistry
from repro.obs.trace import NULL_TRACER
from repro.serving.chaos import NULL_INJECTOR, InjectedFault

from .executor import ExecutorCache
from .lifecycle import RetirementPlan
from .shape_class import (ClassNeed, ClassRegistry, ShapeClass, ShapePolicy,
                          class_requirements, pad_to_class, unpad_from_class)


@dataclasses.dataclass(frozen=True)
class ModelKind:
    """What the engine knows of one served model: a further model is one
    more entry of `MODELS`."""

    # its `ExecutorCache` methods, `<executor>` and `<executor>_batched`
    executor: str
    params_arg: str     # `Engine.register`'s keyword for its parameters
    backends: tuple     # executor backends it runs on
    square: bool        # attends over A's rows, so A must be square
    # parameter pytree -> the shapes the group and executor keys carry
    shapes: Callable[[object], tuple]
    # shapes -> (features in, logits out)
    io_widths: Callable[[tuple], tuple]
    # (f_in, shapes) -> (aggregation widths, [f_in, f_out] matmuls) a
    # request, for the cost prior `Engine.latency_prior`
    cost_widths: Callable[[int, tuple], tuple]
    # shapes -> attention heads over all layers, 0 for none
    heads: Callable[[tuple], int]


# The models `Engine.register` serves: the paper's GCN
# (`core.hybrid_spmm.gcn_forward`, weights [f_in, f_out] a layer) and
# GAT (`core.gat.gat_forward`; a GAT's last layer averages its heads, so
# it gives F' logits).
MODELS = {
    "gcn": ModelKind(
        executor="gcn", params_arg="weights", backends=("xla", "pallas"),
        square=False,
        shapes=lambda ws: tuple(tuple(w.shape) for w in ws),
        io_widths=lambda s: (s[0][0], s[-1][-1]),
        cost_widths=lambda f_in, s: ([f_in] + [w[1] for w in s], s),
        heads=lambda s: 0),
    "gat": ModelKind(
        executor="gat", params_arg="params", backends=("xla",), square=True,
        shapes=gat_param_shapes,
        io_widths=lambda s: (s[0][0][0], s[-1][1][1]),
        cost_widths=lambda f_in, s: ([h * (f + 1) for _, (h, f) in s],
                                     tuple(w for w, _ in s)),
        heads=lambda s: sum(h for _, (h, _f) in s)),
}


@dataclasses.dataclass
class GraphHandle:
    """A registered graph: padded partition + the facts to undo padding."""

    name: str
    part: TriPartition          # padded to the class shapes, device-resident
    meta: PartitionMeta         # original (true n_rows/n_cols/nnz)
    padded_meta: PartitionMeta  # the class's static meta + true nnz stats
    sclass: ShapeClass
    # vertex reorder permutation and its inverse on the device beside
    # ``part``, or None for a graph registered in input order
    perm: Optional[jax.Array]
    inv_perm: Optional[jax.Array]
    # the served model (one of MODELS) and its parameter pytree on the
    # device (GCN: [f_in, f_out] weights a layer), or None
    model: str
    params: object
    param_shapes: tuple = ()    # its `ModelKind.shapes`
    preprocess_s: float = 0.0
    # exact pre-snapping shape requirements, kept so the lifecycle can
    # re-classify this graph on retirement without re-partitioning
    need: Optional[ClassNeed] = None
    # device -> `_Placed` copies for replica lanes bound to other
    # devices, made on a lane's first dispatch (`Engine._placed`)
    copies: dict = dataclasses.field(default_factory=dict)

    @property
    def n_rows(self) -> int:
        return self.meta.n_rows

    @property
    def weights(self) -> Optional[list]:
        """A GCN handle's weight list (its ``params``); None for another
        model."""
        return self.params if self.model == "gcn" else None


class _Placed(NamedTuple):
    """A graph's device arrays on one device."""
    part: object
    params: object
    perm: Optional[jax.Array]
    inv_perm: Optional[jax.Array]


class _EngineReplicaView:
    """One replica's engine-facing view for a `repro.serving.ReplicaSet`.

    Bound to one device. Shares the owning engine's `ClassRegistry`,
    registered graphs, and stack cache (read-mostly state one process
    can serve from), but owns a PRIVATE `ExecutorCache` — executors are
    per-device state, so each replica compiles and warms its own, and
    one replica's compile never invalidates or evicts another's.
    Dispatches route through the engine's ``serve_group_async`` with
    this view's cache and device injected: features, partitions and
    weights are all placed on ``device``, so the executors run there.
    """

    def __init__(self, engine: "Engine", replica_id: int, executors,
                 device):
        self._engine = engine
        self.replica_id = replica_id
        self.executors = executors
        self.device = device

    def group_key(self, name: str, x) -> tuple:
        return self._engine.group_key(name, x)

    def handle(self, name: str):
        return self._engine.handle(name)

    def latency_prior(self, key: tuple, batch: int):
        return self._engine.latency_prior(key, batch)

    def prepare_x(self, name: str, x):
        return self._engine.prepare_x(name, x, device=self.device,
                                      executors=self.executors)

    def serve_group_async(self, requests, prepared=None) -> tuple:
        return self._engine.serve_group_async(
            requests, prepared, executors=self.executors,
            device=self.device)

    def serve_group(self, requests) -> list:
        return self.serve_group_async(requests)[0]


class Engine:
    """Shape-class compiled serving engine for the tri-hybrid SpMM/GCN."""

    def __init__(self, *, policy: ShapePolicy = ShapePolicy(),
                 partition_cfg: PartitionConfig = PartitionConfig(tile=64),
                 backend: str = "xla", block_cols: int = 0,
                 ell_dispatch: str = "ragged", executor_max_entries: int = 128,
                 max_stacks: int = 32, autotune_cache: Optional[str] = None):
        self.policy = policy
        self.partition_cfg = partition_cfg
        self.registry = ClassRegistry(policy)
        self.executors = ExecutorCache(backend=backend, block_cols=block_cols,
                                       ell_dispatch=ell_dispatch,
                                       max_entries=executor_max_entries)
        self._graphs: dict = {}
        # serve_group member stacks, keyed by the canonicalized member-
        # name tuple: partitions/weights don't change between register
        # calls, so a repeat group reuses its stacked pytrees zero-copy.
        # Bounded LRU (a hit moves the stack to MRU, eviction drops the
        # least-recently-served stack — the hottest repeated group can
        # never be evicted by a parade of one-off groups); re-registering
        # a name evicts its entries.
        if max_stacks < 1:
            raise ValueError(f"max_stacks must be >= 1, got {max_stacks}")
        self._stacks: collections.OrderedDict = collections.OrderedDict()
        self._max_stacks = max_stacks
        # Guards the stack cache: pipelined staging workers may run
        # serve_group_async concurrently with each other and with user
        # infer() calls. Per-member padding stays outside the lock (no
        # shared state); only the OrderedDict bookkeeping is inside. It
        # also guards the handles' per-device copies (`_placed`).
        self._stack_lock = threading.Lock()
        # Stack-cache telemetry on the unified metrics registry
        # (repro.obs.metrics); the legacy int attributes survive as
        # read-only properties below. Increments happen under
        # _stack_lock, which keeps the hit/miss/evict triple coherent.
        self.metrics = MetricsRegistry()
        self._stack_hits = Counter("engine.stack_hits", self.metrics)
        self._stack_misses = Counter("engine.stack_misses", self.metrics)
        self._stack_evictions = Counter("engine.stack_evictions",
                                        self.metrics)
        # Bytes of request features `_pad_x` put on a device.
        self._h2d_bytes = Counter("engine.h2d_bytes", self.metrics)
        # Requests whose row permutation `_pad_x` ran on the device.
        self._device_permutes = Counter("engine.device_permutes",
                                        self.metrics)
        # GAT requests' attention work, over layers x heads: the edge
        # slots their classes score (padding included) and the real
        # edges among them (`_count_attention`).
        self._attn_edge_slots = Counter("engine.attn_edge_slots",
                                        self.metrics)
        self._attn_edges = Counter("engine.attn_edges", self.metrics)
        # Request tracer (repro.obs.trace): off by default; a serving
        # frontend constructed with `tracer=` calls `attach_tracer`,
        # which also fans the tracer out to the executor cache and the
        # autotuner so cache.hit/miss and sweep instants land in the
        # same ring.
        self.tracer = NULL_TRACER
        # Chaos injector (repro.serving.chaos): off by default; a
        # frontend constructed with `injector=` calls `attach_injector`,
        # which fans it out to the executor caches (the compile-failure
        # site). Sites owned here: "dispatch" (raise at enqueue),
        # "poison" (mark one member's name; outputs for poisoned names
        # come back non-finite), "hang" (completion meta never ready).
        self.injector = NULL_INJECTOR
        self._frontend = None   # attached repro.serving.RequestQueue
        self._lifecycle = None  # attached LifecycleManager
        # Per-replica executor caches handed out by replica_view();
        # lifecycle retirement must invalidate a retired class in EVERY
        # one (after drain_class quiesced all replica pipelines).
        self._replica_views: dict = {}
        self._replica_caches: list = []
        # Ragged-kernel autotuner (lazy — first autotune() call builds
        # it). ``autotune_cache`` names the on-disk winner cache.
        self._autotune_cache = autotune_cache
        self._tuner = None

    # Legacy integer reads of the stack-cache counters (tests and the
    # benchmark prints use these; the backing store is the registry).
    @property
    def stack_hits(self) -> int:
        return self._stack_hits.value

    @property
    def stack_misses(self) -> int:
        return self._stack_misses.value

    @property
    def stack_evictions(self) -> int:
        return self._stack_evictions.value

    # --------------------------------------------------------- offline -----
    def register(self, name: str, csr: CSRMatrix, *,
                 reorder: Optional[str] = None, labels=None,
                 weights=None, model: str = "gcn", params=None,
                 part_meta: Optional[tuple] = None) -> GraphHandle:
        """Preprocess one graph into its shape class.

        ``reorder`` names a `repro.core.reorder` strategy (None skips).
        ``model`` names the model ``infer`` / ``serve_batch`` run, one
        of `MODELS`, and ``params`` its parameters: for ``"gcn"`` a
        list of [f_in, f_out] weights (``weights`` is the same), for
        ``"gat"`` one ``{"w", "a_l", "a_r"}`` dict a layer
        (`repro.core.gat`; xla backend only, over a square graph).
        Without them the graph serves ``spmm`` only.
        ``part_meta=(part, meta)`` skips partitioning for callers that
        already ran Algorithm 2 themselves.
        """
        kind = MODELS.get(model)
        if kind is None:
            raise ValueError(f"unknown model {model!r}; serves {list(MODELS)}")
        if weights is not None:
            if model != "gcn" or params is not None:
                raise ValueError("weights= gives a GCN's parameters; pass "
                                 "params= alone")
            params = weights
        shapes = () if params is None else kind.shapes(params)
        if self.executors.backend not in kind.backends:
            raise NotImplementedError(
                f"{model} runs on the {'/'.join(kind.backends)} backend; "
                f"this engine's backend is {self.executors.backend!r}")
        if kind.square and csr.shape[0] != csr.shape[1]:
            raise ValueError(f"{model} attends over a square graph; "
                             f"{name!r} is {csr.shape}")
        t0 = time.perf_counter()
        perm = inv_perm = None
        if part_meta is not None:
            part, meta = part_meta
        else:
            if reorder is not None:
                kw = {"labels": labels} if reorder == "labels" else {}
                csr, perm, _ = reorder_csr(csr, reorder, **kw)
                inv_perm = np.empty_like(perm)
                inv_perm[perm] = np.arange(len(perm))
            part, meta, _ = analyze_and_partition(csr, self.partition_cfg)
        need = class_requirements(part, meta, self.policy)
        sc = self.registry.classify_need(need)
        padded, pmeta = pad_to_class(part, meta, sc)
        # Place the padded partition and the permutation on device once;
        # jit args that are already device arrays are zero-copy on every
        # later call.
        padded, perm, inv_perm = jax.device_put((padded, perm, inv_perm))
        handle = GraphHandle(
            name=name, part=padded, meta=meta, padded_meta=pmeta, sclass=sc,
            perm=perm, inv_perm=inv_perm, model=model,
            params=None if params is None else jtu.tree_map(jnp.asarray,
                                                            list(params)),
            param_shapes=shapes,
            preprocess_s=time.perf_counter() - t0, need=need)
        if params is not None:
            # compile this graph's staging at its `infer` widths now, so
            # its first request does not
            f_in, classes = kind.io_widths(shapes)
            self._stage(self.executors, handle,
                        jnp.zeros((meta.n_cols, f_in), jnp.float32), perm)
            self._unstage(self.executors, handle,
                          jnp.zeros((sc.n_row_tiles * sc.tile, classes),
                                    jnp.float32), inv_perm)
        self._graphs[name] = handle
        # a re-registered name invalidates every cached group stack that
        # contains it — otherwise serve_batch would keep serving the old
        # partition/weights
        with self._stack_lock:
            self._stacks = collections.OrderedDict(
                (k, v) for k, v in self._stacks.items() if name not in k)
        return handle

    def handle(self, name: str) -> GraphHandle:
        return self._graphs[name]

    def replica_view(self, i: int) -> _EngineReplicaView:
        """The per-replica engine view a `repro.serving.ReplicaSet` lane
        drives: bound to ``jax.devices()[i]``, with shared registry and
        graphs (copied to that device on first use) and a private
        `ExecutorCache` (same backend/dispatch configuration as the
        engine's own). Idempotent per index — a lane's cache survives
        re-wiring."""
        view = self._replica_views.get(i)
        if view is None:
            devices = jax.devices()
            if not 0 <= i < len(devices):
                raise ValueError(
                    f"replica {i} needs its own device, but JAX sees "
                    f"{len(devices)}")
            ex = self.executors
            cache = ExecutorCache(backend=ex.backend,
                                  block_cols=ex.block_cols,
                                  ell_dispatch=ex.ell_dispatch,
                                  max_entries=ex.max_entries)
            cache.tracer = self.tracer
            cache.injector = self.injector
            self._replica_caches.append(cache)
            view = self._replica_views[i] = _EngineReplicaView(
                self, i, cache, devices[i])
        return view

    def _placed(self, h: GraphHandle, device) -> _Placed:
        """``h``'s device arrays on ``device``; None means where
        ``register`` put them. A device's copy is made once, on its
        first dispatch."""
        if device is None:
            return _Placed(h.part, h.params, h.perm, h.inv_perm)
        with self._stack_lock:
            placed = h.copies.get(device)
            if placed is None:
                placed = h.copies[device] = jax.device_put(
                    _Placed(h.part, h.params, h.perm, h.inv_perm), device)
        return placed

    # ---------------------------------------------------------- online -----
    def _pad_x(self, h: GraphHandle, x, device=None,
               ex: Optional[ExecutorCache] = None) -> jnp.ndarray:
        """Request features on ``device`` (None: the default device),
        permuted + zero-padded there to the class input rows by ``ex``'s
        (None: the engine's) staging function."""
        x = np.asarray(x, np.float32)
        if x.shape[0] != h.meta.n_cols:
            raise ValueError(
                f"request features have {x.shape[0]} rows; graph "
                f"{h.name!r} expects {h.meta.n_cols}")
        tr = self.tracer
        # PjRt starts its host transpose into the device layout here.
        with tr.span("h2d", "engine", {"bytes": x.nbytes}):
            xd = (jnp.asarray(x) if device is None
                  else jax.device_put(x, device))
        self._h2d_bytes.inc(x.nbytes)
        perm = self._placed(h, device).perm
        with tr.span("pad", "engine"):
            xd = self._stage(self.executors if ex is None else ex, h, xd,
                             perm)
        if perm is not None:
            self._device_permutes.inc()
        return xd

    def _unpad_y(self, h: GraphHandle, y, device=None,
                 ex: Optional[ExecutorCache] = None) -> jnp.ndarray:
        with self.tracer.span("unpad", "engine"):
            return self._unstage(self.executors if ex is None else ex, h, y,
                                 self._placed(h, device).inv_perm)

    @staticmethod
    def _stage(ex: ExecutorCache, h: GraphHandle, xd, perm):
        """``xd`` [n_cols, f] permuted + zero-padded on its device to the
        rows of ``h``'s class, read at call time (a lifecycle
        re-classification changes it)."""
        sc = h.sclass
        if perm is None and xd.shape[0] == sc.n_col_tiles * sc.tile:
            return xd
        return ex.stage(sc, *xd.shape, perm is not None)(xd, perm)

    @staticmethod
    def _unstage(ex: ExecutorCache, h: GraphHandle, y, inv_perm):
        """Class-padded output ``y`` sliced to ``h``'s rows and
        un-permuted, on its device."""
        if inv_perm is None and y.shape[0] == h.n_rows:
            return y
        return ex.unstage(h.sclass, h.n_rows, y.shape[1],
                          inv_perm is not None)(y, inv_perm)

    def spmm(self, name: str, b) -> jnp.ndarray:
        """Y = A @ B through the cached shape-class executor."""
        h = self._graphs[name]
        fn = self.executors.spmm(h.sclass, int(b.shape[1]))
        return self._unpad_y(h, fn(h.part, self._pad_x(h, b)))

    # -------------------------------------------------------- autotune -----
    def autotune(self, name: str, f: int, *, timer=None) -> dict:
        """Tune the ragged ELL kernel for ``name``'s shape class at
        feature width ``f`` and apply the winner to the class.

        Runs the offline sweep in `repro.kernels.autotune` (contract-
        checked candidates only — the oracle rejects illegal ones before
        timing; a cached winner skips the sweep) and installs the config
        via ``ExecutorCache.set_tuned``, invalidating the class's stale
        executors so the next dispatch launches tuned. Tuned outputs are
        bitwise-equal to defaults. Returns the applied config ({} =
        defaults were already optimal or the class has no ELL units).
        ``timer`` injects a deterministic measurement for tests.
        """
        from repro.kernels.autotune import Autotuner
        h = self._graphs[name]
        if self._tuner is None or timer is not None:
            self._tuner = Autotuner(cache_path=self._autotune_cache,
                                    timer=timer)
            self._tuner.tracer = self.tracer
        cfg = self._tuner.tune(h.sclass, int(f))
        self.executors.set_tuned(h.sclass, cfg)
        return cfg

    def infer(self, name: str, x) -> jnp.ndarray:
        """Logits of the graph's registered model (its ``model``: the
        GCN or GAT) for one request's features ``x`` [n_cols, f_in]."""
        h = self._graphs[name]
        key = self._group_key(h, x)
        xp = self._pad_x(h, x)
        with self.tracer.span("launch", "engine"):
            fn = self._forward(self.executors, key)
            y = fn(h.part, xp, h.params)
        self._count_attention(h)
        return self._unpad_y(h, y)

    def _group_key(self, h: GraphHandle, x) -> tuple:
        if h.params is None:
            raise ValueError(
                f"graph {h.name!r} ({h.model}) was registered without "
                f"its parameters: register it with "
                f"{MODELS[h.model].params_arg}= to infer")
        return (h.sclass, int(x.shape[1]), h.param_shapes, h.model)

    def group_key(self, name: str, x) -> tuple:
        """The (shape class, f_in, parameter shapes, model) tuple that
        decides which requests may share one ``serve_group`` dispatch.
        The serving frontend groups on exactly this — single source of
        truth, so frontend grouping can never drift from what
        ``serve_group`` accepts."""
        return self._group_key(self._graphs[name], x)

    @staticmethod
    def _forward(ex: ExecutorCache, key: tuple, batch: int = 0):
        """``ex``'s forward executor of group ``key``: one graph, or
        ``batch`` stacked graphs (vmapped) when ``batch`` > 0."""
        sc, f_in, shapes, model = key
        name = MODELS[model].executor
        if batch:
            return getattr(ex, name + "_batched")(sc, f_in, shapes, batch)
        return getattr(ex, name)(sc, f_in, shapes)

    def _count_attention(self, h: GraphHandle) -> None:
        """Add one request of ``h``, if a GAT, to the attention
        counters: the edge slots its class scores (padding included) and
        the graph's real edges, each over layers x heads."""
        heads = MODELS[h.model].heads(h.param_shapes)
        if not heads:
            return
        sc = h.sclass
        slots = (sc.n_dense_tiles * sc.tile * sc.tile
                 + sc.ell_units * sc.r_block * sc.ell_kmax + sc.coo_nnz)
        self._attn_edge_slots.inc(heads * slots)
        self._attn_edges.inc(heads * h.meta.nnz)

    def serve_batch(self, requests) -> list:
        """Serve [(name, x), ...]; returns logits in request order.

        Requests are grouped by (shape class, feature width, weight
        shapes); each group is dispatched through ``serve_group``, so a
        group of any size costs one launch.
        """
        groups: dict = {}
        for i, (name, x) in enumerate(requests):
            key = self._group_key(self._graphs[name], x)
            groups.setdefault(key, []).append((i, name, x))
        results: list = [None] * len(requests)
        for members in groups.values():
            ys = self.serve_group([(name, x) for _, name, x in members])
            for (i, _, _), y in zip(members, ys):
                results[i] = y
        return results

    def serve_group(self, requests) -> list:
        """One-launch dispatch of a same-key group [(name, x), ...].

        Every request must share (shape class, feature width, weight
        shapes) — ``serve_batch`` and the serving frontend's scheduler
        both guarantee this by construction. The group is stacked
        leaf-wise and run through one vmapped executor; outputs return
        in request order (as JAX's usual unresolved async values — the
        caller blocks when it reads them).
        """
        return self.serve_group_async(requests)[0]

    def prepare_x(self, name: str, x, device=None,
                  executors: Optional[ExecutorCache] = None) -> jnp.ndarray:
        """Stage one request's features: place them on ``device``
        (None: the default device) and permute + pad them there to the
        graph's class input rows, with ``executors``' (None: the
        engine's) staging function. Per-request work that touches
        shared state only under locks (`_placed`, the cache's), so
        pipelined staging workers may run it concurrently; the result
        feeds ``serve_group_async``'s ``prepared`` argument to move this
        cost off the ordered enqueue step."""
        return self._pad_x(self._graphs[name], x, device, executors)

    def serve_group_async(self, requests, prepared=None, *,
                          executors=None, device=None) -> tuple:
        """Non-blocking ``serve_group``: stage + enqueue, don't wait.

        Returns ``(outs, meta)``: ``outs`` are the per-request outputs
        as *unresolved* device values (JAX async dispatch — the XLA
        execution may still be running), and ``meta`` is the completion
        contract for a pipelined caller:

          ``cold``      this dispatch built (traced + compiled) at least
                        one executor — its wall time must not feed warm
                        latency EWMAs;
          ``ready()``   True once every output's device buffer exists
                        (non-blocking poll);
          ``complete()``  block until the outputs are ready.

        ``prepared`` optionally carries pre-staged padded features
        (`prepare_x`, aligned with ``requests``) so a staging pool can
        parallelize the padding while the enqueue itself stays ordered.
        ``executors`` and ``device`` are a replica lane's private
        `ExecutorCache` and device (what `replica_view` dispatches
        through); None uses the engine's own cache and the arrays where
        ``register`` placed them.
        """
        ex = executors if executors is not None else self.executors
        if not requests:
            return [], {"cold": False, "ready": lambda: True,
                        "complete": lambda: None}
        inj = self.injector
        if inj.enabled:
            spec = inj.poll("dispatch")
            if spec is not None:
                raise InjectedFault("dispatch",
                                    transient=spec.mode == "transient")
            spec = inj.poll("poison")
            if spec is not None:
                inj.mark_poisoned(requests[spec.member % len(requests)][0])
        members = []
        key0 = None
        for i, (name, x) in enumerate(requests):
            h = self._graphs[name]
            key = self._group_key(h, x)
            if key0 is None:
                key0 = key
            elif key != key0:
                raise ValueError(
                    f"serve_group members must share one (class, f_in, "
                    f"parameter shapes, model) key; {requests[0][0]!r} "
                    f"and {name!r} differ")
            xp = prepared[i] if prepared is not None else None
            members.append((i, h, x, xp))
        # Deliberate unguarded builds-counter read: a stale value only
        # over-reports cold, which skips a warm sample and never poisons
        # the latency EWMA — see _completion_meta.
        builds0 = ex.builds  # lint: racy-ok(cold-detect delta; over-reports only)

        def pad(h, x, xp):
            return xp if xp is not None else self._pad_x(h, x, device, ex)

        tr = self.tracer
        if len(members) == 1:
            i, h, x, xp = members[0]
            args = {"n": 1} if tr.enabled else None
            with tr.span("pad", "engine", args):
                fn = self._forward(ex, key0)
                xpad = pad(h, x, xp)
                placed = self._placed(h, device)
            outs = [self._unpad_y(h, fn(placed.part, xpad, placed.params),
                                  device, ex)]
            self._count_attention(h)
            meta = self._completion_meta(outs, builds0, ex)
            if inj.enabled:
                outs, meta = self._inject_async(inj, requests, outs, meta)
            return outs, meta
        # Canonicalize group order by name so (g0,g1) and (g1,g0)
        # share one cached stack, then pad to the next power-of-two
        # batch (repeating the last member; its extra outputs are
        # dropped) so the set of compiled batch sizes stays
        # logarithmic in traffic, not linear in observed group sizes.
        members.sort(key=lambda m: m[1].name)
        bs = 1 << (len(members) - 1).bit_length()
        padded = members + [members[-1]] * (bs - len(members))
        args = {"n": len(members), "batch": bs} if tr.enabled else None
        with tr.span("pad", "engine", args):
            fn = self._forward(ex, key0, bs)
            # one stack per device: a lane's stack lives where it runs
            stack_key = (device,) + tuple(h.name for _, h, _, _ in padded)
            placed = [self._placed(h, device) for _, h, _, _ in padded]
            with self._stack_lock:
                stacks = self._stacks.get(stack_key)
                if stacks is None:
                    self._stack_misses.inc()
                    part_stack = jtu.tree_map(
                        lambda *leaves: jnp.stack(leaves),
                        *[p.part for p in placed])
                    params_stack = jtu.tree_map(
                        lambda *leaves: jnp.stack(leaves),
                        *[p.params for p in placed])
                    while len(self._stacks) >= self._max_stacks:
                        self._stacks.popitem(last=False)       # LRU out
                        self._stack_evictions.inc()
                    stacks = (part_stack, params_stack)
                    self._stacks[stack_key] = stacks
                else:
                    self._stacks.move_to_end(stack_key)        # mark MRU
                    self._stack_hits.inc()
            part_stack, params_stack = stacks
            # each member is staged once; the batch's filler slots
            # repeat the last member's staged features
            xs = [pad(h, x, xp) for _, h, x, xp in members]
            x_stack = jnp.stack(xs + [xs[-1]] * (bs - len(members)))
        ys = fn(part_stack, x_stack, params_stack)
        results: list = [None] * len(members)
        for j, (i, h, _, _) in enumerate(members):
            results[i] = self._unpad_y(h, ys[j], device, ex)
            self._count_attention(h)
        meta = self._completion_meta(results, builds0, ex)
        if inj.enabled:
            results, meta = self._inject_async(inj, requests, results, meta)
        return results, meta

    def _inject_async(self, inj, requests, outs, meta) -> tuple:
        """Apply post-enqueue chaos sites to one dispatch's results:
        poisoned member names yield non-finite outputs (every dispatch,
        so quarantine bisection can isolate them), and a fired "hang"
        spec makes the completion meta never ready — only the dispatch
        watchdog can reclaim the slot."""
        if inj.poisoned_names():
            outs = [y * float("nan") if inj.is_poisoned(nm) else y
                    for (nm, _), y in zip(requests, outs)]
        spec = inj.poll("hang")
        if spec is not None:
            def hung_complete():
                raise InjectedFault(
                    "hang", detail="completion forced on a hung dispatch")
            meta = dict(meta)
            meta["ready"] = lambda: False
            meta["complete"] = hung_complete
        return outs, meta

    def _completion_meta(self, outs, builds0: int, ex=None) -> dict:
        """The async-dispatch completion contract for one enqueued group.

        ``cold`` is a ``builds`` delta on the cache that served the
        dispatch (a replica view's own, or the engine's): under
        concurrent staging a sibling's miss can be misattributed, which
        only *over*-reports cold — a skipped warm sample, never a
        poisoned EWMA.
        """
        if ex is None:
            ex = self.executors

        def ready() -> bool:
            return all(getattr(y, "is_ready", lambda: True)() for y in outs)

        def complete() -> None:
            for y in outs:
                blocker = getattr(y, "block_until_ready", None)
                if blocker is not None:
                    blocker()

        return {"cold": ex.builds > builds0,  # lint: racy-ok(cold-detect delta; over-reports only)
                "ready": ready, "complete": complete}

    # --------------------------------------------------------- latency -----
    def latency_prior(self, key: tuple, batch: int) -> Optional[float]:
        """Roofline-derived warm-latency prior for one group dispatch.

        Seeds the serving frontend's `LatencyModel` for keys with no
        observations yet: the class's padded MAC capacity (the slots the
        kernels *execute*, including masked lanes) and its array bytes
        give a FLOPs/bytes roofline bound at the measured-peak constants
        in `repro.analysis.roofline`, floored at a fixed per-launch
        overhead so an arithmetic-light class never forecasts an
        implausibly instant dispatch (which would make the scheduler
        linger past its deadline). Returns None for keys whose class
        lacks capacity metadata (e.g. the simulation's stub classes),
        and on a device whose kind has no published peaks — the model
        then learns from observations, starting at its flat default.
        """
        from repro.analysis.roofline import PEAKS
        sc = key[0]
        peaks = PEAKS.get(jax.devices()[0].device_kind)
        if peaks is None or not hasattr(sc, "ell_mac_capacity"):
            return None
        f_in = key[1]
        kind = MODELS[key[3] if len(key) > 3 else "gcn"]
        widths, w_shapes = kind.cost_widths(
            f_in, key[2] if len(key) > 2 else ())
        macs = (sc.ell_mac_capacity
                + sc.n_dense_tiles * sc.tile * sc.tile + sc.coo_nnz)
        n_rows = sc.n_row_tiles * sc.tile
        # per layer: one aggregation over the class's slots at each
        # width + the dense weight GEMM
        flops = 2.0 * macs * sum(widths)
        flops += sum(2.0 * n_rows * a * b for a, b in w_shapes)
        byts = 4.0 * (macs + n_rows * sum(widths))
        t = max(flops / peaks.flops, byts / peaks.hbm_bw) * max(int(batch), 1)
        return max(t, self.LAUNCH_FLOOR_S)

    # Floor for the roofline prior: per-dispatch launch/host overhead no
    # capacity model predicts. Deliberately conservative — a too-small
    # first estimate closes batches too late and misses deadlines.
    LAUNCH_FLOOR_S = 2e-3

    # ----------------------------------------------------------- stats -----
    def attach_tracer(self, tracer) -> None:
        """Install a `repro.obs.trace.Tracer` and fan it out to the
        engine's sub-components (executor cache; the autotuner when it
        exists) so engine-side spans and instants land in the same ring
        as the serving frontend's. `RequestQueue(..., tracer=...)` calls
        this; passing `NULL_TRACER` turns engine tracing back off."""
        self.tracer = tracer
        self.executors.tracer = tracer
        for cache in self._replica_caches:
            cache.tracer = tracer
        if self._tuner is not None:
            self._tuner.tracer = tracer

    def attach_injector(self, injector) -> None:
        """Install a `repro.serving.chaos.ChaosInjector` and fan it out
        to every executor cache (the compile-failure site lives in
        `ExecutorCache._get`). Mirrors ``attach_tracer``; passing
        `NULL_INJECTOR` turns injection back off."""
        self.injector = injector
        self.executors.injector = injector
        for cache in self._replica_caches:
            cache.injector = injector

    def attach_frontend(self, frontend) -> None:
        """Register a serving frontend (`repro.serving.RequestQueue`) so
        its `ServerStats` surface through ``stats()["serving"]``. One
        frontend slot: attaching replaces the previous one, so a
        secondary/throwaway queue over the same engine should pass
        ``RequestQueue(..., attach=False)``."""
        self._frontend = frontend

    def class_waste_by_class(self) -> dict:
        """Per-shape-class padded-MAC waste, keyed by ShapeClass object:
        members' true nnz vs the class's padded capacity, per engine
        slice.

        ``ell_capacity`` counts the MAC slots the ragged kernel actually
        executes per member (Kmax × units × r_block — masked lanes are
        dead trips, not skipped ones), so ``ell_waste_frac`` is the
        fraction of ELL kernel work spent on padding. This is the drift
        signal the lifecycle manager acts on: a class whose rolling
        waste stays above budget is retired and its members re-founded
        tighter (`repro.engine.lifecycle`).
        """
        agg: dict = {}
        for h in self._graphs.values():
            d = agg.setdefault(h.sclass, {
                "members": 0, "ell_nnz": 0, "dense_nnz": 0, "coo_nnz": 0})
            d["members"] += 1
            d["ell_nnz"] += h.meta.nnz_ell
            d["dense_nnz"] += h.meta.nnz_dense
            d["coo_nnz"] += h.meta.nnz_coo
        out: dict = {}
        for sc, d in agg.items():
            m = d["members"]
            caps = {
                "ell_capacity": sc.ell_mac_capacity * m,
                "dense_capacity": sc.n_dense_tiles * sc.tile * sc.tile * m,
                "coo_capacity": sc.coo_nnz * m,
            }
            true_total = d["ell_nnz"] + d["dense_nnz"] + d["coo_nnz"]
            cap_total = sum(caps.values())
            entry = dict(d)
            entry.update(caps)
            entry["ell_waste_frac"] = (
                1.0 - d["ell_nnz"] / caps["ell_capacity"]
                if caps["ell_capacity"] else 0.0)
            entry["padded_mac_waste_frac"] = (
                1.0 - true_total / cap_total if cap_total else 0.0)
            out[sc] = entry
        return out

    def class_waste(self) -> dict:
        """`class_waste_by_class` rendered with summary-string keys —
        the JSON-able ``stats()["class_waste"]`` block."""
        return {sc.summary(): entry
                for sc, entry in self.class_waste_by_class().items()}

    def class_traffic(self) -> dict:
        """Cumulative executor lookups per ShapeClass (lifecycle input),
        summed over the engine's own cache and every replica view's."""
        out = collections.Counter(self.executors.traffic_by_class())
        for cache in self._replica_caches:
            out.update(cache.traffic_by_class())
        return dict(out)

    # ------------------------------------------------------- lifecycle -----
    def attach_lifecycle(self, manager) -> None:
        """Register a `repro.engine.lifecycle.LifecycleManager` so its
        counters surface through ``stats()["lifecycle"]``. One slot,
        like ``attach_frontend``."""
        self._lifecycle = manager

    def members_of(self, sc: ShapeClass) -> list:
        """Names of every registered graph currently padded into ``sc``."""
        return [h.name for h in self._graphs.values() if h.sclass == sc]

    def plan_retirement(self, sc: ShapeClass) -> Optional[RetirementPlan]:
        """Plan (without mutating anything) the re-classing that
        retiring ``sc`` implies.

        Members are re-fit largest-first — first into surviving live
        classes under the normal fit rules, then into tight
        (growth=1.0) classes founded for this plan — so the biggest
        member founds the successor and its smaller siblings join it
        instead of each founding their own. Returns None when ``sc``
        has no members (nothing to re-class; the registry can just
        drop it).
        """
        members = [h for h in self._graphs.values() if h.sclass == sc]
        if not members:
            return None
        members.sort(key=lambda h: (
            -(h.need.ell_kmax * h.need.ell_units * h.need.r_block
              + h.need.n_dense_tiles * h.need.tile * h.need.tile
              + h.need.coo_nnz),
            h.name))
        targets, new = self.registry.plan_reclass(
            [h.need for h in members], exclude=(sc,))
        return RetirementPlan(
            sclass=sc, names=tuple(h.name for h in members),
            targets=tuple(targets), new_classes=tuple(new))

    def execute_retirement(self, plan: RetirementPlan) -> dict:
        """Apply a `RetirementPlan`: retire the class in the registry,
        re-pad every member into its successor class, and invalidate
        the retired class's cached executors and member stacks.

        Callers that serve live traffic must drain in-flight batches
        keyed on the retiring class FIRST (`RequestQueue.drain_class`
        runs this as its ``action`` under the queue lock) — after this
        returns, ``group_key`` routes the members to their successor
        classes and the old executors are gone.
        """
        sc = plan.sclass
        self.registry.retire(sc)
        moved = []
        for name, target in zip(plan.names, plan.targets):
            h = self._graphs.get(name)
            if h is None or h.sclass != sc:
                continue    # re-registered since planning; already moved on
            self.registry.admit(target)
            part = unpad_from_class(h.part, h.padded_meta, h.meta)
            padded, pmeta = pad_to_class(part, h.meta, target)
            h.part = jax.device_put(padded)
            h.copies = {}       # replica copies hold the old padding
            h.padded_meta = pmeta
            h.sclass = target
            moved.append(name)
        invalidated = self.executors.invalidate_class(sc)
        # every replica's private cache holds its own executors for the
        # retired class; drain_class already quiesced all replica
        # pipelines, so no lane can be mid-dispatch on a stale key here
        for cache in self._replica_caches:
            invalidated += cache.invalidate_class(sc)
        # cached member stacks hold the OLD padded arrays of moved
        # graphs — any stack containing one is stale
        moved_set = set(moved)
        with self._stack_lock:
            self._stacks = collections.OrderedDict(
                (k, v) for k, v in self._stacks.items()
                if not moved_set.intersection(k))
        return {"members": len(moved),
                "executors_invalidated": invalidated,
                "new_classes": len(plan.new_classes)}

    def stats(self) -> dict:
        classes = {h.sclass for h in self._graphs.values()}
        cache = self.executors.stats_snapshot()
        # the stack-cache counters are mutated by staging workers under
        # _stack_lock; snapshot them under the same lock so the rollup
        # is coherent
        with self._stack_lock:
            stack = {"stacks": len(self._stacks),
                     "stack_hits": self.stack_hits,
                     "stack_misses": self.stack_misses,
                     "stack_evictions": self.stack_evictions}
        out = {
            "graphs": len(self._graphs),
            "shape_classes": len(classes),
            "executors": self.executors.size,
            "executor_max_entries": self.executors.max_entries,
            "cache_hits": cache["hits"],
            "cache_misses": cache["misses"],
            "cache_evictions": cache["evictions"],
            "per_class": self.executors.class_stats(),
            "staging": self.executors.staging_snapshot(),
            "stack_max": self._max_stacks,
            "class_waste": self.class_waste(),
            "registry": self.registry.stats(),
            "h2d_bytes": self._h2d_bytes.value,
            "device_permutes": self._device_permutes.value,
            "attn_edge_slots": self._attn_edge_slots.value,
            "attn_edges": self._attn_edges.value,
            **stack,
        }
        if self._tuner is not None:
            out["autotune"] = self._tuner.stats()
        if self._frontend is not None:
            out["serving"] = self._frontend.stats.snapshot()
        if self._lifecycle is not None:
            out["lifecycle"] = self._lifecycle.snapshot()
        return out

    def summary(self) -> str:
        s = self.stats()
        return (f"Engine: {s['graphs']} graphs in {s['shape_classes']} "
                f"shape classes; {self.executors.summary()}")
