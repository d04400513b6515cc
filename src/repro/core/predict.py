"""Serving path: fused multiclass scoring + batched request queue.

The paper's end product is a model that is cheap to *evaluate* — merging
exists precisely so the SV bank stays small enough for fast prediction
(Picard 2018 builds budgeted SV banks expressly for high-throughput batched
scoring).  This module is the inference half of that bargain:

  * ``ServeModel`` — the exported, inference-only view of a trained
    ``SVMState``: the (C, slots, dim) SV bank (optionally quantized to
    bfloat16 — halves the bank's HBM and gather traffic), fp32 alphas with
    the active-count mask FOLDED IN at export time (inactive slots zeroed
    once, so the hot scoring path carries no masking), and the kernel width.
    Binary models export as C = 1 with ``binary=True`` (labels are ±1 signs
    instead of argmax ids).
  * ``predict_labels`` — ONE fused scoring program per microbatch: a single
    ``rbf_matrix`` launch against the flattened (C * slots, dim) bank
    (``kernels.ops.class_scores``, the same fold ``class_kernel_rows`` uses
    for training margins), fp32 alpha accumulation, argmax on device.
  * ``BatchQueue`` — microbatch assembly for a request stream: rows from
    submitted requests are packed into full ``max_batch`` microbatches in
    arrival order (a request may span microbatches; a microbatch may span
    requests), and the ragged tail pads up to a power-of-two *bucket* so the
    jit/pjit cache holds at most ``len(buckets)`` compiled shapes.  Because
    each row's scores depend only on that row and the bank, queue labels are
    bitwise the labels of one direct ``predict_labels`` call on the same
    rows — any arrival pattern, any bucket geometry (pinned by
    ``tests/core/test_serve_predict.py``).
  * ``load_serve_model`` — reads a ``fit_stream`` / ``fit_multiclass_stream``
    checkpoint (``repro.checkpoint`` layout) straight into a ``ServeModel``:
    the state template is reconstructed from the manifest's recorded leaf
    shapes/dtypes, so serving needs no training config object.

The distributed form (bank replicated per device, requests sharded over
every mesh axis — zero-collective scoring) is ``core.distributed``'s
``layout="serve"``; ``launch.serve --arch svm_bsgd`` is the driver and
``benchmarks/bench_serve.py`` the throughput/latency artifact.
"""
from __future__ import annotations

import dataclasses
import json
import os
import threading
import time
from collections import deque
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from .bsgd import SVMState
from ..kernels import ops as kops


@dataclasses.dataclass(frozen=True)
class ServeModel:
    """Inference-only view of a trained budgeted SVM.

    Attributes:
      sv_x: (C, slots, dim) SV bank in the serving dtype (``bank_dtype`` at
        export; bfloat16 halves bank HBM).  Binary models are C = 1.
      alpha: (C, slots) float32 coefficients with inactive slots already
        zeroed — scoring never masks.
      count: (C,) int32 active-SV watermarks (reporting only).
      gamma: () float32 RBF width.
      binary: static — True when the model was a binary ``SVMState``; labels
        are then ±1 signs (``bsgd.predict`` convention) instead of argmax
        class ids.
    """

    sv_x: jax.Array
    alpha: jax.Array
    count: jax.Array
    gamma: jax.Array
    binary: bool = False

    @property
    def n_classes(self) -> int:
        return self.sv_x.shape[0]

    @property
    def label_dtype(self):
        return np.float32 if self.binary else np.int32


jax.tree_util.register_dataclass(
    ServeModel, ["sv_x", "alpha", "count", "gamma"], ["binary"])


def export_model(state: SVMState, gamma, *, bank_dtype=None) -> ServeModel:
    """Trained ``SVMState`` (binary or stacked multiclass) -> ``ServeModel``.

    ``bank_dtype`` quantizes the SV bank (e.g. ``"bfloat16"``); alphas are
    always carried in float32 and accumulation in scoring stays fp32, so
    quantization touches only the kernel's inputs.  The active-count mask is
    folded into alpha here — exactly the ``where(active, alpha, 0)`` the
    training-side decision functions apply per call.
    """
    binary = state.sv_x.ndim == 2
    sv_x, alpha, count = state.sv_x, state.alpha, state.count
    if binary:
        sv_x, alpha, count = sv_x[None], alpha[None], count[None]
    active = jnp.arange(alpha.shape[-1])[None, :] < count[:, None]
    alpha = jnp.where(active, alpha, 0.0).astype(jnp.float32)
    if bank_dtype is not None:
        sv_x = sv_x.astype(jnp.dtype(bank_dtype))
    return ServeModel(sv_x=sv_x, alpha=alpha,
                      count=count.astype(jnp.int32),
                      gamma=jnp.asarray(gamma, jnp.float32), binary=binary)


def serve_scores(model: ServeModel, x, *, impl: str = "auto"):
    """Per-class decision scores for a request batch: (n, d) -> (C, n).

    One fused kernel launch against the flattened (C * slots, dim) bank with
    fp32 accumulation (``kernels.ops.class_scores``).
    """
    return kops.class_scores(x, model.sv_x, model.alpha, model.gamma,
                             impl=impl)


@partial(jax.jit, static_argnames=("impl",))
def predict_labels(model: ServeModel, x, *, impl: str = "auto"):
    """The fused serve cell: labels for a request batch, argmax on device.

    Multiclass models return (n,) int32 class ids; binary models return the
    (n,) float32 ±1 signs of ``bsgd.predict``.
    """
    with jax.named_scope("predict_labels"):
        scores = serve_scores(model, x, impl=impl)
        if model.binary:
            return jnp.sign(scores[0]).astype(jnp.float32)
        return jnp.argmax(scores, axis=0).astype(jnp.int32)


@partial(jax.jit, static_argnames=("k", "impl"))
def top_k_labels(model: ServeModel, x, *, k: int = 1, impl: str = "auto"):
    """Top-k class ids + decision scores per request row.

    x: (n, d) -> ``(ids, scores)`` of shape (n, k): per row, the k classes
    with the highest one-vs-rest decision scores, best first (ties broken by
    the lower class id, exactly like the argmax in ``predict_labels`` — so
    ``ids[:, 0]`` is bitwise ``predict_labels``).  One fused scoring launch;
    only the final ``lax.top_k`` is new work.  Multiclass models only: a
    binary model has one score, rank it yourself from ``serve_scores``.
    """
    if model.binary:
        raise ValueError("top_k_labels needs a multiclass model; binary "
                         "models have a single ±1 decision (predict_labels)")
    if not 1 <= k <= model.n_classes:
        raise ValueError(f"k={k} not in [1, n_classes={model.n_classes}]")
    scores = serve_scores(model, x, impl=impl)            # (C, n)
    vals, ids = jax.lax.top_k(scores.T, k)                # (n, k) each
    return ids.astype(jnp.int32), vals


@partial(jax.jit, static_argnames=("temperature", "impl"))
def predict_proba(model: ServeModel, x, *, temperature: float = 1.0,
                  impl: str = "auto"):
    """Calibrated softmax probabilities over the C class scores: (n, C).

    ``softmax(scores / temperature)`` per row — temperature scaling is the
    standard post-hoc calibration knob (T = 1 is the raw softmax; fit T on a
    held-out split to calibrate confidence).  Rows sum to 1 and the argmax
    is bitwise ``predict_labels`` for any positive temperature.  Multiclass
    models only.  ``temperature`` is static (one compile per distinct value
    — it is a per-deployment calibration constant, not per-request data).
    """
    if model.binary:
        raise ValueError("predict_proba needs a multiclass model")
    # T = 0 would be a silent NaN factory and T < 0 reverses the ranking
    # the docstring promises
    if temperature <= 0:
        raise ValueError(f"temperature={temperature} must be > 0")
    scores = serve_scores(model, x, impl=impl)            # (C, n)
    return jax.nn.softmax(scores.T / temperature, axis=-1)


# ---------------------------------------------------------------------------
# Batched request queue
# ---------------------------------------------------------------------------

class ServeTimeout(TimeoutError):
    """``take``/``drain`` timed out waiting for resolution.  The message
    names the ticket and the queue's in-flight depth (DESIGN.md §16);
    subclassing ``TimeoutError`` keeps pre-§16 handlers working."""


class ServeDeadline(TimeoutError):
    """A request's own ``deadline_s`` expired before its rows were
    dispatched — the queue shed it instead of serving stale results."""


class QueueFull(RuntimeError):
    """``submit`` refused because ``max_pending`` rows are already queued —
    bounded-pending load shedding instead of unbounded buffering."""


def _validate_request(x: np.ndarray, dim: int | None) -> None:
    """Shared ``submit`` validation (BatchQueue + AsyncBatchQueue): clear
    ``ValueError``s for malformed rows instead of a shape blowup (or a
    silent poisoned score) deep inside a fused microbatch."""
    if x.ndim != 2:
        raise ValueError(f"request must be (n, dim), got shape {x.shape}")
    if x.dtype == np.bool_ or not np.issubdtype(x.dtype, np.number):
        raise ValueError(
            f"request rows must be a numeric dtype, got {x.dtype}")
    if dim is not None and x.shape[1] != dim:
        raise ValueError(
            f"request dim {x.shape[1]} != model dim {dim}")
    if x.size and not np.isfinite(x).all():
        raise ValueError(
            "request rows contain non-finite values — refused at submit so "
            "a poisoned request can never surface as a non-finite score")


def default_buckets(max_batch: int, min_bucket: int = 8) -> tuple[int, ...]:
    """Power-of-two pad targets up to (and always including) ``max_batch``."""
    if min_bucket < 1:
        raise ValueError(f"min_bucket={min_bucket} < 1")
    buckets = []
    b = min_bucket
    while b < max_batch:
        buckets.append(b)
        b *= 2
    buckets.append(max_batch)
    return tuple(buckets)


def pad_bucket(n: int, buckets) -> int:
    """The smallest bucket that fits ``n`` rows (ascending ``buckets``; the
    largest bucket is the fallback for ``n > max``).  THE pad-target rule —
    shared by ``BatchQueue``, ``AsyncBatchQueue`` and ``drive_trace`` so the
    compiled-shape set can never silently diverge between them."""
    for b in buckets:
        if n <= b:
            return b
    return buckets[-1]


class BatchQueue:
    """Microbatch assembly over a request stream, one fused cell per batch.

    Requests (``(n_i, dim)`` row blocks) are packed into ``max_batch``-row
    microbatches in arrival order; a full microbatch runs immediately at
    ``submit`` (host memory stays O(max_batch), not O(stream)), and
    ``drain`` flushes the ragged remainder padded up to the smallest bucket
    that fits — so the set of compiled shapes is exactly ``buckets``, never
    one-per-request-size.  Pad rows are zeros and their labels are dropped;
    every real row's label is bitwise what one direct ``predict_labels``
    call on the concatenated stream would produce.

    ``predict_fn`` overrides the compute (the distributed serve path passes
    a pjit'd cell over the mesh — ``make_distributed_predict``); it must map
    a (b, dim) device/host array to (b,) labels.  Per-microbatch wall times
    (including dispatch + host sync) land in ``latencies_s`` for the bench.
    """

    def __init__(self, model: ServeModel, *, max_batch: int = 256,
                 min_bucket: int = 8, impl: str = "auto", predict_fn=None):
        if max_batch < 1:
            raise ValueError(f"max_batch={max_batch} < 1")
        self.model = model
        self.max_batch = max_batch
        self.buckets = default_buckets(max_batch, min_bucket)
        self._predict = (predict_fn if predict_fn is not None
                         else partial(predict_labels, model, impl=impl))
        self._pending: deque = deque()   # (ticket, rows ndarray, row_offset)
        self._pending_rows = 0
        self._need: dict[int, int] = {}          # ticket -> total rows
        self._parts: dict[int, list] = {}        # ticket -> [(offset, labels)]
        self._done: dict[int, np.ndarray] = {}
        self._next_ticket = 0
        self.latencies_s: list[float] = []
        self.stats = {"rows": 0, "microbatches": 0, "padded_rows": 0,
                      "bucket_counts": {}, "bucket_real_rows": {}}

    def warmup(self, dtype=np.float32) -> None:
        """Pay every bucket shape's compile up front (honest tail latencies).

        Runs the queue's OWN ``predict_fn`` — a warm call through any other
        route can still miss the jit cache (a static arg passed explicitly
        and the same value as a default key separate entries).
        """
        dim = self.model.sv_x.shape[-1]
        for b in self.buckets:
            jax.block_until_ready(self._predict(np.zeros((b, dim), dtype)))

    def _bucket_for(self, n: int) -> int:
        return pad_bucket(n, self.buckets)

    def submit(self, x) -> int:
        """Enqueue one request of rows; returns its ticket."""
        x = np.asarray(x)
        _validate_request(x, self.model.sv_x.shape[-1])
        ticket = self._next_ticket
        self._next_ticket += 1
        self._need[ticket] = x.shape[0]
        self._parts[ticket] = []
        if x.shape[0] == 0:
            self._finish(ticket)
        else:
            self._pending.append((ticket, x, 0))
            self._pending_rows += x.shape[0]
        while self._pending_rows >= self.max_batch:
            self._run_microbatch(self.max_batch)
        return ticket

    def drain(self) -> None:
        """Flush the ragged tail (padded to its bucket); all tickets resolve."""
        while self._pending_rows >= self.max_batch:
            self._run_microbatch(self.max_batch)
        if self._pending_rows:
            self._run_microbatch(self._pending_rows)

    def take(self, ticket: int) -> np.ndarray:
        """Labels for a resolved ticket (``drain`` first for partial tails)."""
        if ticket not in self._done:
            raise KeyError(f"ticket {ticket} not resolved — drain() first")
        return self._done.pop(ticket)

    def _finish(self, ticket: int) -> None:
        parts = sorted(self._parts.pop(ticket), key=lambda p: p[0])
        got = np.concatenate([p[1] for p in parts]) if parts else \
            np.zeros((0,), self.model.label_dtype)
        assert got.shape[0] == self._need.pop(ticket)
        self._done[ticket] = got

    def _run_microbatch(self, n_real: int) -> None:
        pad_to = self._bucket_for(n_real)
        slices, rows = [], []
        need = n_real
        while need:
            ticket, x, off = self._pending.popleft()
            take = min(need, x.shape[0])
            rows.append(x[:take])
            slices.append((ticket, off, take))
            if take < x.shape[0]:
                self._pending.appendleft((ticket, x[take:], off + take))
            need -= take
        self._pending_rows -= n_real
        xb = np.concatenate(rows) if len(rows) > 1 else rows[0]
        if pad_to > n_real:
            xb = np.concatenate(
                [xb, np.zeros((pad_to - n_real, xb.shape[1]), xb.dtype)])
        t0 = time.perf_counter()
        labels = self._predict(xb)
        labels = np.asarray(jax.block_until_ready(labels))
        self.latencies_s.append(time.perf_counter() - t0)
        self.stats["rows"] += n_real
        self.stats["microbatches"] += 1
        self.stats["padded_rows"] += pad_to - n_real
        self.stats["bucket_counts"][pad_to] = \
            self.stats["bucket_counts"].get(pad_to, 0) + 1
        self.stats["bucket_real_rows"][pad_to] = \
            self.stats["bucket_real_rows"].get(pad_to, 0) + n_real
        pos = 0
        for ticket, off, take in slices:
            self._parts[ticket].append((off, labels[pos:pos + take]))
            pos += take
            done = sum(p[1].shape[0] for p in self._parts[ticket])
            if done == self._need[ticket]:
                self._finish(ticket)


def serve_requests(model: ServeModel, requests, **queue_kw) -> list[np.ndarray]:
    """Convenience wrapper: run a whole request list through a fresh
    ``BatchQueue``; returns per-request label arrays in submission order."""
    q = BatchQueue(model, **queue_kw)
    tickets = [q.submit(r) for r in requests]
    q.drain()
    return [q.take(t) for t in tickets]


# ---------------------------------------------------------------------------
# Versioned model bank + continuous-batching async queue
# ---------------------------------------------------------------------------

class ModelBank:
    """A versioned, atomically hot-swappable ``ServeModel`` slot.

    The seam between a streaming trainer and a live serve queue:
    ``fit_stream(bank=..., publish_every=K)`` publishes an immutable snapshot
    every K chunks, and an ``AsyncBatchQueue`` built over the bank picks up
    the newest version per microbatch WITHOUT draining — hot-swap mid-trace.

    The slot is one ``(version, model)`` tuple swapped by a single reference
    assignment, so readers always see a consistent pair (never version *n*
    with model *n+1*); versions are strictly monotone.  ``ServeModel``s are
    immutable (frozen dataclass over immutable jax arrays), so a published
    snapshot can never change under a reader — the publisher's job is to
    hand over arrays nobody mutates or donates afterwards (the trainers copy
    out of their donated buffers first; see ``bsgd._make_publish``).
    """

    def __init__(self, model: ServeModel | None = None):
        self._slot = (1 if model is not None else 0, model)
        self._cv = threading.Condition()

    @property
    def version(self) -> int:
        """Version of the current model (0 = empty bank)."""
        return self._slot[0]

    def publish(self, model: ServeModel) -> int:
        """Swap in ``model`` as the new current version; returns it."""
        with self._cv:
            version = self._slot[0] + 1
            self._slot = (version, model)       # one atomic reference swap
            self._cv.notify_all()
        return version

    def current(self) -> tuple[int, ServeModel]:
        """The live ``(version, model)`` pair (lock-free hot path)."""
        slot = self._slot
        if slot[1] is None:
            raise LookupError("ModelBank is empty — publish() a model first")
        return slot

    def wait(self, version: int = 1,
             timeout: float | None = None) -> tuple[int, ServeModel]:
        """Block until the bank holds at least ``version``; returns the pair
        (raises TimeoutError on ``timeout``)."""
        with self._cv:
            if not self._cv.wait_for(lambda: self._slot[0] >= version,
                                     timeout):
                raise TimeoutError(
                    f"ModelBank still at version {self._slot[0]} < {version} "
                    f"after {timeout}s")
            return self._slot


# a request's life in the queue, and a dispatcher round, stage by stage
_REQUEST = obs.Chain(("serve.wait", "serve.inflight", "serve.handoff"),
                     ("ticket",))
_LAUNCH = obs.Chain(("serve.assemble", "serve.launch"), ("rows", "bucket"))
_RESOLVE = obs.Chain(("serve.sync", "serve.scatter"))


class AsyncBatchQueue:
    """Continuous batching: a dispatcher thread owns the device, submitters
    never compute.

    ``submit`` is thread-safe and returns a ticket immediately — rows land
    in a pending ring and the dispatcher assembles microbatches out of
    WHATEVER is pending whenever the device frees up (up to ``max_batch``
    rows per launch, ragged tails coalesced across requests before padding,
    arrival order preserved).  Two launches are kept in flight: while
    microbatch *i* executes, the dispatcher assembles AND dispatches *i+1*,
    then resolves *i* — host assembly, the host↔device sync, and the label
    scatter all overlap device compute instead of serializing with it (the
    ``BatchQueue`` gap this class exists to close).  Dispatch is
    WAITER-GATED: a microbatch launches only when a full ``max_batch`` is
    pending, or someone is blocked in ``take``/``drain``, or the queue is
    closing.  Submit-ahead traces therefore coalesce into full launches
    instead of trickling out as many small ones (the dispatcher never does
    MORE launches than a sync ``BatchQueue`` would for the same trace),
    while a live caller blocking on its ticket still gets its rows
    dispatched immediately — no artificial batching delay where latency
    matters.

    Each row's scores depend only on that row and the bank, so labels are
    BITWISE one direct ``predict_labels`` call on the same rows for any
    arrival pattern/interleaving (same guarantee, and same pad-bucket rule
    — ``pad_bucket`` — as ``BatchQueue``).

    ``model`` may be a ``ServeModel`` (fixed) or a ``ModelBank``: with a
    bank, the dispatcher re-reads ``bank.current()`` per microbatch, so a
    version published mid-trace is picked up at the next launch without
    draining — every row of one microbatch is scored by exactly one version
    (recorded in ``stats["versions"]``).  The single-model predict path is
    AOT-compiled per bucket shape (``predict_labels.lower(...).compile()``)
    — hot-swapped snapshots share the executables because shapes/dtypes
    don't change across versions.  ``predict_fn`` overrides compute exactly
    as in ``BatchQueue`` (fixed model only — the distributed serve path).

    ``take``/``drain`` block until resolution (optional ``timeout``); a
    dispatcher failure re-raises on the caller's thread, never hangs.  Use
    as a context manager or call ``close()`` — pending work is flushed, the
    thread joins.

    Overload protection (DESIGN.md §16): ``max_pending`` bounds the pending
    row buffer — ``submit`` beyond it raises ``QueueFull`` immediately
    (load shedding) instead of buffering without bound.  A per-request
    ``submit(..., deadline_s=...)`` sheds the request if its rows are still
    undispatched when the deadline passes: ``take`` then raises
    ``ServeDeadline``.  ``take``/``drain`` timeouts raise ``ServeTimeout``
    naming the ticket and the in-flight depth.  All three are typed results,
    never hangs — a supervisor can catch and retry/degrade.

    Spans (``repro.obs``), all under one root per queue: per request, from
    ``take``, ``serve.wait`` (submit to the launch carrying its last rows),
    ``serve.inflight`` (that launch to its labels scattered) and
    ``serve.handoff`` (scattered to ``take`` returning), each with its
    ``ticket``; on the dispatcher thread ``serve.idle``, ``serve.assemble``
    and ``serve.launch`` (``rows``, ``bucket``), ``serve.compile`` on an
    executable-cache miss, ``serve.sync`` and ``serve.scatter``.  The
    request and round stages are ``obs.Chain`` records, which cost the
    queue a fraction of what a span each would.  ``warmup`` records none.
    """

    def __init__(self, model: ServeModel | ModelBank, *, max_batch: int = 256,
                 min_bucket: int = 8, impl: str = "auto", predict_fn=None,
                 max_pending: int | None = None):
        if max_batch < 1:
            raise ValueError(f"max_batch={max_batch} < 1")
        if max_pending is not None and max_pending < max_batch:
            raise ValueError(f"max_pending={max_pending} < "
                             f"max_batch={max_batch} could never fill "
                             "a full microbatch")
        self._bank = model if isinstance(model, ModelBank) else None
        self.model = None if self._bank is not None else model
        if self._bank is not None and predict_fn is not None:
            raise ValueError("predict_fn requires a fixed ServeModel — a "
                             "ModelBank swaps models per microbatch")
        self.max_batch = max_batch
        self.max_pending = max_pending
        self.buckets = default_buckets(max_batch, min_bucket)
        self._impl = impl
        self._predict_fn = predict_fn
        self._compiled: dict = {}     # (bucket, bank signature) -> executable
        self._cv = threading.Condition()
        # (ticket, rows, row_offset, deadline, submit ns)
        self._pending: deque = deque()
        self._pending_rows = 0
        self._need: dict[int, int] = {}
        self._parts: dict[int, list] = {}
        self._done: dict[int, np.ndarray] = {}
        # ticket -> (submit, last launch, scattered) ns, for its spans
        self._times: dict[int, tuple] = {}
        self._root = obs.new_root()
        self._root_id = self._root.root_id
        self._dead: dict[int, str] = {}   # ticket -> shed reason
        self._next_ticket = 0
        self._unresolved = 0
        self._waiters = 0
        self._error: BaseException | None = None
        self._stop = False
        self.latencies_s: list[float] = []
        self.stats = {"rows": 0, "microbatches": 0, "padded_rows": 0,
                      "bucket_counts": {}, "bucket_real_rows": {},
                      "versions": {}}
        self._thread = threading.Thread(target=self._dispatch_loop,
                                        daemon=True, name="serve-dispatch")
        self._thread.start()

    # -- submitter side ------------------------------------------------------

    def submit(self, x, *, deadline_s: float | None = None) -> int:
        """Enqueue one request of rows; returns its ticket immediately.

        ``deadline_s``: optional per-request budget (seconds from now).  If
        the rows are still undispatched when it expires, the request is shed
        and ``take`` raises ``ServeDeadline`` instead of returning stale
        labels.  Raises ``QueueFull`` when ``max_pending`` rows are already
        buffered (bounded-pending load shedding).
        """
        t_submit = obs.now_ns()
        x = np.asarray(x)
        try:
            dim = self._current()[1].sv_x.shape[-1]
        except LookupError:
            dim = None                     # empty bank — no dim to pin yet
        _validate_request(x, dim)
        dl = (None if deadline_s is None
              else time.monotonic() + float(deadline_s))
        with self._cv:
            self._check_error()
            if self._stop:
                raise RuntimeError("AsyncBatchQueue is closed")
            if (self.max_pending is not None and x.shape[0]
                    and self._pending_rows + x.shape[0] > self.max_pending):
                raise QueueFull(
                    f"{self._pending_rows} rows pending + {x.shape[0]} new "
                    f"> max_pending={self.max_pending} — request shed")
            ticket = self._next_ticket
            self._next_ticket += 1
            self._need[ticket] = x.shape[0]
            self._parts[ticket] = []
            if x.shape[0] == 0:
                self._done[ticket] = np.zeros((0,), self._label_dtype())
                self._times[ticket] = (t_submit,) * 3
                self._need.pop(ticket)
                self._parts.pop(ticket)
            else:
                self._unresolved += 1
                self._pending.append((ticket, x, 0, dl, t_submit))
                self._pending_rows += x.shape[0]
                # only wake the dispatcher when the gate is actually open
                # (full batch, or a waiter already blocked) — an
                # unconditional notify would bounce it awake on every
                # sub-batch submit just to re-check and sleep
                if self._pending_rows >= self.max_batch or self._waiters:
                    self._cv.notify_all()
            return ticket

    def take(self, ticket: int, timeout: float | None = None) -> np.ndarray:
        """Labels for a ticket; blocks until its last microbatch resolves.

        Raises ``ServeDeadline`` if the ticket was shed (its ``deadline_s``
        expired undispatched), ``ServeTimeout`` on ``timeout``.
        """
        def ready():
            return ticket in self._done or ticket in self._dead

        def timed_out():
            raise ServeTimeout(
                f"ticket {ticket} unresolved after {timeout}s "
                f"({self._unresolved} requests in flight, "
                f"{self._pending_rows} rows pending)")

        self._await(ready, timeout, timed_out)
        with self._cv:
            if ticket in self._dead:
                raise ServeDeadline(
                    f"ticket {ticket} shed: {self._dead.pop(ticket)}")
            labels = self._done.pop(ticket)
            t_submit, t_launch, t_scatter = self._times.pop(ticket)
        _REQUEST.record((t_submit, t_launch, t_scatter, obs.now_ns()),
                        self._root_id, ticket)
        return labels

    def drain(self, timeout: float | None = None) -> None:
        """Block until every submitted row is scored, resolved or shed."""
        def ready():
            return self._unresolved == 0

        def timed_out():
            raise ServeTimeout(
                f"{self._unresolved} requests unresolved after {timeout}s "
                f"({self._pending_rows} rows pending)")

        self._await(ready, timeout, timed_out)

    def _await(self, ready, timeout, timed_out) -> None:
        """Wait (as a gate-opening waiter) until ``ready()`` under the lock,
        re-checking at request deadlines so shed tickets surface without a
        dispatcher wakeup; calls ``timed_out()`` past ``timeout``."""
        deadline_t = None if timeout is None else time.monotonic() + timeout
        with self._cv:
            self._waiters += 1          # un-gate dispatch of partial batches
            self._cv.notify_all()
            try:
                while True:
                    self._purge_expired_locked()
                    self._check_error()
                    if ready():
                        return
                    now = time.monotonic()
                    if deadline_t is not None and now >= deadline_t:
                        timed_out()
                    bounds = [t for t in (deadline_t,
                                          self._earliest_deadline_locked())
                              if t is not None]
                    self._cv.wait(max(min(bounds) - now, 0.0) + 1e-3
                                  if bounds else None)
            finally:
                self._waiters -= 1

    def close(self, timeout: float | None = 30.0) -> None:
        """Flush pending work, stop and join the dispatcher (idempotent)."""
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._thread.join(timeout)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False

    def warmup(self, dtype=np.float32) -> None:
        """Pay every bucket shape's compile up front (honest tail latencies).

        Compiles through the queue's OWN per-bucket path (the AOT executable
        cache, or the caller's ``predict_fn``) — see ``BatchQueue.warmup``
        for the jit-cache-key footgun this sidesteps.
        """
        version, model = self._current()
        dim = model.sv_x.shape[-1]
        for b in self.buckets:
            xb = np.zeros((b, dim), dtype)
            sig = self._sig(model, xb, b)
            if self._predict_fn is None and sig not in self._compiled:
                self._compile(model, xb, sig)   # here, not as serve.compile
            jax.block_until_ready(self._score(model, xb, b))

    # -- dispatcher side -----------------------------------------------------

    def _check_error(self) -> None:
        if self._error is not None:
            raise RuntimeError("AsyncBatchQueue dispatcher failed") \
                from self._error

    def _label_dtype(self):
        try:
            return self._current()[1].label_dtype
        except LookupError:
            return np.int32

    def _current(self) -> tuple:
        if self._bank is not None:
            return self._bank.current()
        return None, self.model

    @staticmethod
    def _sig(model: ServeModel, xb: np.ndarray, bucket: int) -> tuple:
        return (bucket, str(xb.dtype), model.sv_x.shape,
                str(model.sv_x.dtype), model.binary)

    def _compile(self, model: ServeModel, xb: np.ndarray, sig: tuple):
        fn = predict_labels.lower(model, xb, impl=self._impl).compile()
        self._compiled[sig] = fn
        return fn

    def _score(self, model: ServeModel, xb: np.ndarray, bucket: int):
        """One microbatch launch (async dispatch — no host sync here)."""
        if self._predict_fn is not None:
            return self._predict_fn(xb)
        sig = self._sig(model, xb, bucket)
        fn = self._compiled.get(sig)
        if fn is None:
            with obs.span("serve.compile"):
                fn = self._compile(model, xb, sig)
        return fn(model, xb)

    def _earliest_deadline_locked(self) -> float | None:
        dls = [e[3] for e in self._pending if e[3] is not None]
        return min(dls) if dls else None

    def _purge_expired_locked(self) -> None:
        """Shed pending requests whose deadline passed (caller holds the
        lock): the ticket is marked dead, its undispatched rows dropped, and
        ``take`` raises ``ServeDeadline`` for it.  In-flight slices of a
        shed ticket resolve into the void (``_resolve`` skips dead)."""
        if self._earliest_deadline_locked() is None:
            return
        now = time.monotonic()
        kept: deque = deque()
        shed = False
        for entry in self._pending:
            ticket, x, _, dl, _ = entry
            if dl is None or now < dl:
                kept.append(entry)
                continue
            shed = True
            self._pending_rows -= x.shape[0]
            self._dead[ticket] = (
                f"deadline expired with {x.shape[0]} rows undispatched")
            self._need.pop(ticket, None)
            self._parts.pop(ticket, None)
            self._unresolved -= 1
        if shed:
            self._pending = kept
            self._cv.notify_all()

    def _pop_rows_locked(self):
        """Take up to ``max_batch`` live pending rows (caller holds the
        lock); expired requests are shed first, never launched."""
        self._purge_expired_locked()
        n_real = min(self._pending_rows, self.max_batch)
        rows, slices, need = [], [], n_real
        while need:
            ticket, x, off, dl, t_submit = self._pending.popleft()
            take = min(need, x.shape[0])
            rows.append(x[:take])
            slices.append((ticket, off, take, t_submit))
            if take < x.shape[0]:
                self._pending.appendleft(
                    (ticket, x[take:], off + take, dl, t_submit))
            need -= take
        self._pending_rows -= n_real
        return rows, slices, n_real

    def _launch(self, rows, slices, n_real):
        """Assemble + dispatch one microbatch (outside the lock)."""
        pad_to = pad_bucket(n_real, self.buckets)
        # a fixed model needs no bank read in the hot loop
        version, model = ((None, self.model) if self._bank is None
                          else self._bank.current())
        # each stage's clock readings lie inside its annotation, so that an
        # annotation's own cost (its first on a thread allocates) is outside
        with obs.annotate("serve.assemble", rows=n_real, bucket=pad_to):
            t_assemble = obs.now_ns()
            xb = np.zeros((pad_to, rows[0].shape[1]), rows[0].dtype)
            pos = 0
            for r in rows:
                xb[pos:pos + r.shape[0]] = r
                pos += r.shape[0]
        with obs.annotate("serve.launch"):
            t_launch = obs.now_ns()
            t0 = time.perf_counter()
            labels = self._score(model, xb, pad_to)
            t_end = obs.now_ns()
        _LAUNCH.record((t_assemble, t_launch, t_end), self._root_id, n_real,
                       pad_to)
        return labels, slices, n_real, pad_to, version, t0, t_launch

    def _resolve(self, inflight) -> None:
        """Sync one launch, scatter its labels, resolve finished tickets."""
        labels, slices, n_real, pad_to, version, t0, t_launch = inflight
        with obs.annotate("serve.sync"):
            t_sync = obs.now_ns()
            labels = np.asarray(labels)           # blocks until scored
        lat = time.perf_counter() - t0
        with obs.annotate("serve.scatter"):
            t_resolve = obs.now_ns()
            parts_by_slice = []                   # slice outside the lock
            pos = 0
            for _, _, take, _ in slices:
                parts_by_slice.append(labels[pos:pos + take])
                pos += take
            with self._cv:
                t_scatter = obs.now_ns()
                self.latencies_s.append(lat)
                st = self.stats
                st["rows"] += n_real
                st["microbatches"] += 1
                st["padded_rows"] += pad_to - n_real
                st["bucket_counts"][pad_to] = \
                    st["bucket_counts"].get(pad_to, 0) + 1
                st["bucket_real_rows"][pad_to] = \
                    st["bucket_real_rows"].get(pad_to, 0) + n_real
                if version is not None:
                    st["versions"][version] = \
                        st["versions"].get(version, 0) + 1
                for (ticket, off, take, t_submit), part in zip(slices,
                                                               parts_by_slice):
                    if ticket in self._dead:
                        continue   # shed mid-flight — drop its labels
                    need = self._need[ticket]
                    if off == 0 and take == need:     # single-part fast path
                        self._done[ticket] = part
                        self._times[ticket] = (t_submit, t_launch, t_scatter)
                        self._need.pop(ticket)
                        self._parts.pop(ticket)
                        self._unresolved -= 1
                        continue
                    parts = self._parts[ticket]
                    parts.append((off, part))
                    if sum(p[1].shape[0] for p in parts) == need:
                        parts.sort(key=lambda p: p[0])
                        self._done[ticket] = np.concatenate(
                            [p[1] for p in parts])
                        self._times[ticket] = (t_submit, t_launch, t_scatter)
                        self._need.pop(ticket)
                        self._parts.pop(ticket)
                        self._unresolved -= 1
                self._cv.notify_all()
            t_end = obs.now_ns()
        _RESOLVE.record((t_sync, t_resolve, t_end), self._root_id)

    def _dispatch_loop(self) -> None:
        inflight = None

        # dispatchable = a full batch pends, or someone is blocked on the
        # result (take/drain/close) — partial batches otherwise coalesce
        def dispatchable():
            return self._pending_rows and (
                self._pending_rows >= self.max_batch
                or self._waiters or self._stop)

        def idle():
            return (not dispatchable() and not self._stop
                    and inflight is None)

        try:
            with obs.attach(self._root):
                while True:
                    batch = None
                    with self._cv:
                        if idle():
                            with obs.span("serve.idle"):
                                while idle():
                                    self._cv.wait()
                        if (self._stop and not self._pending_rows
                                and inflight is None):
                            return
                        if dispatchable():
                            batch = self._pop_rows_locked()
                    # dispatch the NEXT microbatch before syncing the previous:
                    # the device is never idle while the host scatters labels
                    # (a purge can shed every pending row — then there is
                    # nothing to launch)
                    launched = (self._launch(*batch)
                                if batch is not None and batch[2] else None)
                    if inflight is not None:
                        self._resolve(inflight)
                    inflight = launched
        except BaseException as e:  # noqa: BLE001 — surfaced to callers
            with self._cv:
                self._error = e
                self._cv.notify_all()


def ragged_trace_sizes(total_rows: int, max_batch: int, rng) -> list[int]:
    """A deterministic ragged request-size trace summing to ``total_rows``
    (sizes drawn in [1, max_batch] from the caller's ``rng``)."""
    sizes, left = [], total_rows
    while left:
        s = int(min(left, rng.integers(1, max_batch + 1)))
        sizes.append(s)
        left -= s
    return sizes


def drive_trace(model: ServeModel, req_x, sizes, *, max_batch: int = 256,
                min_bucket: int = 8, impl: str = "auto", predict_fn=None,
                queue: str = "sync") -> dict:
    """Push one request trace through a fresh warmed queue and measure it.

    The shared serve-loop used by ``launch.serve_svm`` and
    ``benchmarks.bench_serve``: submits ``sizes``-shaped requests from
    ``req_x`` in order, drains, ASSERTS the labels are bitwise one direct
    ``predict_labels`` call (the parity gate runs on every invocation), and
    returns rows/sec + p50/p99 microbatch latency + queue stats —
    including ``pad_waste_frac`` (fraction of scored rows that were
    padding) and per-bucket ``bucket_occupancy`` (real rows / bucket
    capacity), which make tail padding at non-power-of-two traces visible.

    ``queue="async"`` drives the same trace through an ``AsyncBatchQueue``
    (continuous batching; same parity gate) — with a ``ModelBank`` in
    ``model``, its CURRENT snapshot anchors the parity call even if the
    bank keeps moving mid-trace (per-row labels are version-consistent,
    so parity is asserted only on a fixed model).
    """
    bank = model if isinstance(model, ModelBank) else None
    fixed = bank is None
    if queue == "async":
        q = AsyncBatchQueue(model, max_batch=max_batch,
                            min_bucket=min_bucket, impl=impl,
                            predict_fn=predict_fn)
    elif queue == "sync":
        if bank is not None:
            raise ValueError("queue='sync' needs a fixed ServeModel")
        q = BatchQueue(model, max_batch=max_batch, min_bucket=min_bucket,
                       impl=impl, predict_fn=predict_fn)
    else:
        raise ValueError(f"queue={queue!r}: expected 'sync' or 'async'")
    q.warmup()
    t0 = time.perf_counter()
    tickets, off = [], 0
    for s in sizes:
        tickets.append(q.submit(req_x[off:off + s]))
        off += s
    q.drain()
    labels = np.concatenate([q.take(t) for t in tickets])
    wall = time.perf_counter() - t0
    if queue == "async":
        q.close()
    if fixed:
        direct = np.asarray(predict_labels(model, req_x[:off], impl=impl))
        assert (labels == direct).all(), "queue/direct parity violated"
    lat = np.asarray(q.latencies_s)
    padded = q.stats["padded_rows"]
    occupancy = {
        b: round(q.stats["bucket_real_rows"].get(b, 0) / (n * b), 4)
        for b, n in sorted(q.stats["bucket_counts"].items())
    }
    out = {
        "rows": off, "requests": len(sizes), "queue": queue,
        "bank_dtype": str((bank.current()[1] if bank is not None
                           else model).sv_x.dtype),
        "rows_per_s": round(off / wall, 1),
        "microbatches": q.stats["microbatches"],
        "padded_rows": padded,
        "pad_waste_frac": round(padded / (off + padded), 4) if off else 0.0,
        "bucket_counts": q.stats["bucket_counts"],
        "bucket_occupancy": occupancy,
        "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
        "p99_ms": round(float(np.percentile(lat, 99)) * 1e3, 3),
    }
    if queue == "async" and q.stats["versions"]:
        out["versions"] = {int(k): v for k, v in q.stats["versions"].items()}
    return out


# ---------------------------------------------------------------------------
# Checkpoint -> ServeModel
# ---------------------------------------------------------------------------

def load_serve_model(ckpt_dir: str, gamma, *, step: int | None = None,
                     bank_dtype=None) -> ServeModel:
    """Export a ``ServeModel`` straight from a training checkpoint.

    Works on any ``repro.checkpoint`` directory whose tree carries an
    ``SVMState`` under the ``state`` key — which is exactly what
    ``fit_stream`` / ``fit_multiclass_stream`` write (mid-epoch checkpoints
    included: serving ignores the epoch cursor/carry leaves).  The state
    template is rebuilt from the manifest's recorded leaf shapes/dtypes, so
    no training config is needed; binary vs multiclass is inferred from the
    bank's rank.  ``gamma`` is a hyperparameter, not a checkpointed array —
    pass the training value.
    """
    from .. import checkpoint as ckpt

    if step is None:
        step = ckpt.latest_step(ckpt_dir)
        if step is None:
            raise ValueError(f"{ckpt_dir}: no complete checkpoint found")
    manifest = os.path.join(ckpt_dir, f"step_{step:08d}", "manifest.json")
    try:
        with open(manifest) as f:
            leaves = json.load(f).get("leaves")
    except FileNotFoundError:
        raise ValueError(f"{ckpt_dir}: step {step} has no manifest — not a "
                         "complete checkpoint") from None
    except json.JSONDecodeError as e:
        raise ValueError(f"{ckpt_dir}: step {step} manifest is corrupt "
                         f"({e})") from None
    if not isinstance(leaves, dict):
        raise ValueError(f"{ckpt_dir}: step {step} manifest records no "
                         "leaves — not a checkpoint this library wrote")
    needed = ("state/sv_x", "state/alpha", "state/count", "state/step",
              "state/n_inserts", "state/n_merges")
    missing = [k for k in needed if k not in leaves]
    if missing:
        raise ValueError(
            f"{ckpt_dir}: step {step} is not an SVM training checkpoint "
            f"(missing leaves {missing})")

    def sds(key):
        spec = leaves[key]
        return jax.ShapeDtypeStruct(tuple(spec["shape"]),
                                    jnp.dtype(spec["dtype"]))

    template = SVMState(
        sv_x=sds("state/sv_x"), alpha=sds("state/alpha"),
        count=sds("state/count"), step=sds("state/step"),
        n_inserts=sds("state/n_inserts"), n_merges=sds("state/n_merges"),
        kmat=sds("state/kmat") if "state/kmat" in leaves else None)
    state = ckpt.load(ckpt_dir, step, {"state": template})["state"]
    return export_model(state, gamma, bank_dtype=bank_dtype)
