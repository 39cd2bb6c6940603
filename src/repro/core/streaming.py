"""Semi-streaming driver for Algorithm 1 (the paper's streaming model).

The graph's edge list lives outside accelerator memory (numpy arrays, memmap
or any chunk iterator); only O(n) node state (alive bitmap, degree vector,
best set) is held.  Each pass streams the edges chunk by chunk through a
bounded-in-flight async pipeline: at most ``prefetch`` chunks are resident
at any time, chunk reads (memmap I/O) and device degree kernels overlap in
the worker pool, and the host reduces completed chunks strictly in stream
order — exactly the paper's "store and update the current node degrees"
loop, but out-of-core for the edges AND for the pipeline.

Production concerns implemented here (this is the fault-tolerance layer for
the paper's own workload):
  * per-pass atomic checkpointing of the O(n) state -> restart resumes
    mid-algorithm after a crash; the checkpoint write itself is deferred
    into the next pass's pipeline window (overlapped with chunk work);
  * straggler mitigation: chunks are dispatched to a worker pool and the
    slowest tail is speculatively re-issued (Hadoop-style backup tasks);
    results are idempotent so first-completion wins;
  * exception safety: a failing chunk worker re-raises its REAL error
    (never a downstream ``KeyError``); with ``speculative`` on, a failed
    attempt is retried once and a still-running duplicate may complete the
    chunk first (first success wins).  A failing pass never loses the
    previous pass's completed checkpoint;
  * out-of-core compaction: with ``spill_dir`` set, the geometric ladder's
    rebuilt survivor stream is written to disk-backed memmaps instead of
    host RAM, so streams whose SURVIVORS exceed memory still ride the
    amortized-O(m) ladder; the spill participates in checkpoint/resume.
"""

from __future__ import annotations

import os
import shutil
import threading
import warnings
from concurrent.futures import FIRST_COMPLETED, Future, ThreadPoolExecutor, wait
from dataclasses import dataclass, field
from typing import Callable, Dict, Iterator, Optional, Set, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import constants, faults

Chunk = Tuple[np.ndarray, np.ndarray, np.ndarray]  # (src, dst, weight)

# How many times a FAILED chunk (no success yet, no duplicate in flight) is
# re-issued before its error surfaces.  Counted separately from straggler
# speculation, so a speculated chunk keeps its full retry budget; a
# deterministic error still surfaces after one retry instead of looping.
_MAX_FAILURE_RETRIES = 1


@jax.jit
def _chunk_stats(src, dst, w, alive):
    """Partial (degree vector, total weight, alive edge count) for one edge
    chunk.

    Accumulates in float32 regardless of the incoming weight dtype so the
    chunk reduction is stable for low-precision edge streams (bf16/f16
    weights) and identical across chunkings.  The degree count itself is
    the engine's :func:`~repro.core.engine.segment_degree_count` (§5.2's
    reduce-side count exists once); the alive edge count feeds the
    geometric compaction trigger."""
    from repro.core.engine import segment_degree_count

    ok = alive[src] & alive[dst]
    w_alive = jnp.where(ok, w.astype(jnp.float32), jnp.float32(0.0))
    deg, total = segment_degree_count(src, dst, w_alive, alive.shape[0])
    return deg, total, jnp.sum(ok.astype(jnp.int32))


_PASS_STEP = None


def _pass_step():
    """Jitted Algorithm-1 pass step (lazy: engine imports streaming's
    sibling modules).  ``run()`` syncs the step's two SCALARS (rho, new
    alive count) right away — it needs them for best-tracking and the loop
    condition — so the device step itself is not overlapped; what the jit
    buys is that the O(n) alive-bitmap transfer and the rest of the host
    finalization (scatter, best copy, checkpoint fsync) are deferred into
    the next pass's pipeline window instead of blocking between passes."""
    global _PASS_STEP
    if _PASS_STEP is None:
        from repro.core.engine import undirected_pass_step

        _PASS_STEP = jax.jit(undirected_pass_step, static_argnames=("eps",))
    return _PASS_STEP


class _Deferred:
    """Exactly-once wrapper for a pass's deferred host finalization (runs
    either inside the next pass's pipeline window or at loop exit)."""

    def __init__(self, fn: Callable[[], None]):
        self._fn = fn
        self._ran = False

    def __call__(self) -> None:
        if not self._ran:
            self._ran = True
            self._fn()


@dataclass
class StreamState:
    alive: np.ndarray
    best_alive: np.ndarray
    best_rho: float
    pass_idx: int
    history: list = field(default_factory=list)  # (n_alive, e_alive, rho)


class StreamingDensest:
    """Multi-pass semi-streaming Algorithm 1 with checkpoint/restart.

    ``prefetch`` bounds the number of chunks resident in host memory during
    a pass (the async pipeline's window); ``spill_dir`` redirects the
    geometric ladder's rebuilt streams to disk-backed memmaps;
    ``residency_cap_edges`` is an optional hard bound on the edges the
    driver may hold in host RAM — exceeding it without a ``spill_dir``
    raises instead of silently going in-core.
    """

    def __init__(
        self,
        chunk_stream: Callable[[], Iterator[Chunk]],
        n_nodes: int,
        eps: float = 0.5,
        checkpoint_dir: Optional[str] = None,
        n_workers: int = 4,
        speculative: bool = True,
        speculate_tail_frac: float = 0.2,
        compaction: str = "off",
        prefetch: int = 8,
        spill_dir: Optional[str] = None,
        residency_cap_edges: Optional[int] = None,
    ):
        if compaction not in ("off", "geometric"):
            raise ValueError(
                f"compaction={compaction!r} not in ('off', 'geometric')"
            )
        if prefetch < 1:
            raise ValueError(f"prefetch={prefetch} must be >= 1")
        if spill_dir is not None and compaction != "geometric":
            raise ValueError(
                "spill_dir is the geometric ladder's disk spill; this "
                "driver needs compaction='geometric' to use it"
            )
        self.chunk_stream = chunk_stream
        self.n_nodes = n_nodes
        self.eps = eps
        self.checkpoint_dir = checkpoint_dir
        self.n_workers = n_workers
        self.speculative = speculative
        self.speculate_tail_frac = speculate_tail_frac
        self.compaction = compaction
        self.prefetch = prefetch
        self.spill_dir = spill_dir
        self.residency_cap_edges = residency_cap_edges
        # Observability: speculation and compaction counts plus
        # peak-residency high-water marks.
        self.speculative_reissues = 0
        self.compactions = 0  # geometric: stream rebuilds performed
        self.spill_rungs = 0  # geometric: rebuilds that went to disk
        self.peak_resident_chunks = 0  # max chunks materialized at once
        self.peak_resident_edges = 0  # max edge slots in host RAM at once
        # Edge slots pinned in host RAM by an in-RAM rebuilt stream (0 for
        # the caller's stream and for spilled rebuilds).
        self._stream_resident_edges = 0
        self._cur_rung_dir: Optional[str] = None

    # ----- checkpointing -------------------------------------------------
    def _ckpt_path(self) -> Optional[str]:
        if self.checkpoint_dir is None:
            return None
        return os.path.join(self.checkpoint_dir, "stream_state.npz")

    def _save(self, st: StreamState) -> None:
        """Atomic checkpoint write (:func:`repro.ioutil.atomic_write_file`):
        a crash at any point leaves either the old or the new checkpoint,
        never a torn one."""
        path = self._ckpt_path()
        if path is None:
            return
        from repro.ioutil import atomic_write_file

        faults.fire("streaming.checkpoint_save")
        atomic_write_file(
            path,
            lambda f: np.savez(
                f,
                alive=st.alive,
                best_alive=st.best_alive,
                best_rho=np.float64(st.best_rho),
                pass_idx=np.int64(st.pass_idx),
                history=np.asarray(st.history, np.float64).reshape(-1, 3),
            ),
            suffix=".npz.tmp",
        )

    def _load(self) -> Optional[StreamState]:
        """Fail-open checkpoint read: a corrupt or truncated checkpoint
        (torn copy, bad disk, injected fault) warns, quarantines the bad
        file with ONE atomic rename (``<path>.corrupt`` — kept for the
        operator's post-mortem) and resumes as a fresh run, instead of
        crashing the restart path the checkpoint exists to protect."""
        path = self._ckpt_path()
        if path is None or not os.path.exists(path):
            return None
        try:
            faults.fire("streaming.checkpoint_load")
            z = np.load(path)
            return StreamState(
                alive=z["alive"],
                best_alive=z["best_alive"],
                best_rho=float(z["best_rho"]),
                pass_idx=int(z["pass_idx"]),
                history=[tuple(r) for r in z["history"]],
            )
        except Exception as e:  # noqa: BLE001 — quarantine + start fresh
            quarantine = path + ".corrupt"
            # repro: allow(fault-sites) recovery path of the hooked streaming.checkpoint_load try above
            try:
                # repro: allow(atomic-io) quarantine rename of a corrupt file, not an artifact publish
                os.replace(path, quarantine)
            except OSError:
                quarantine = "<rename failed>"
            warnings.warn(
                f"checkpoint {path} is unreadable "
                f"({type(e).__name__}: {e}); quarantined to {quarantine}, "
                "starting fresh",
                RuntimeWarning,
                stacklevel=2,
            )
            return None

    # ----- one streaming pass --------------------------------------------
    def _pass_stats(
        self,
        alive,
        stream: Optional[Callable[[], Iterator[Chunk]]] = None,
        prelude: Optional[Callable[[], None]] = None,
    ) -> Tuple[np.ndarray, float, int, int]:
        """Streams all chunks once through the bounded async pipeline;
        returns (degree vector, total weight, alive edge count, edge slots
        streamed).

        At most ``prefetch`` chunks are materialized at any moment: chunks
        are pulled lazily from the stream iterator, dispatched to the worker
        pool (chunk reads and device kernels overlap across workers), and
        reduced on the host STRICTLY IN STREAM ORDER as the reduce frontier
        advances — so the result is bit-identical to a synchronous pass for
        every ``prefetch``/``n_workers`` setting and completion order.

        ``prelude`` (the previous pass's deferred finalization: best-set
        bookkeeping + checkpoint fsync) runs right after the first window is
        dispatched, overlapped with chunk work; it runs even if the pass
        fails, so an exploding chunk never loses completed-pass state.

        Failure semantics: a chunk worker's exception is re-raised with its
        real traceback (never a downstream ``KeyError``).  Speculative
        duplicates stay first-success-wins: a failure is ignored while a
        duplicate is in flight or has already succeeded; with
        ``speculative`` on, a failed chunk with no live duplicate is retried
        once before the error surfaces.  ``stream`` defaults to the
        constructor's chunk stream (the compaction ladder substitutes its
        rebuilt, smaller stream).
        """
        alive = jnp.asarray(alive)
        window = max(int(self.prefetch), 1)
        it = iter((stream or self.chunk_stream)())
        deg = np.zeros(alive.shape[0], np.float32)
        total = 0.0
        n_ok = 0
        n_slots = 0
        resident: Dict[int, Chunk] = {}  # materialized, not yet reduced
        done: Dict[int, Tuple[np.ndarray, float, int]] = {}
        inflight: Dict[int, int] = {}
        retries: Dict[int, int] = {}  # failure-triggered re-issues only
        reduced = 0  # the in-order reduce frontier
        resident_edges = 0
        n_seen = 0
        exhausted = False
        speculated = False
        lock = threading.Lock()

        def work(idx: int, chunk: Chunk) -> int:
            # Chaos hook: every ATTEMPT (first issue, speculative duplicate,
            # retry) of a chunk is one hit at this site, keyed by chunk
            # index — so tests drive the real retry/speculation machinery.
            faults.fire("streaming.chunk", key=idx)
            s, d, w = chunk
            dd, tt, cc = _chunk_stats(
                jnp.asarray(s), jnp.asarray(d), jnp.asarray(w), alive
            )
            out = (np.asarray(dd), float(tt), int(cc))
            with lock:
                # First completion wins (idempotent); a late duplicate of an
                # already-reduced chunk must not re-enter ``done``.
                if idx not in done and idx in resident:
                    done[idx] = out
            return idx

        prelude_ran = prelude is None
        try:
            with ThreadPoolExecutor(max_workers=self.n_workers) as ex:
                pending: Set[Future] = set()
                futmap: Dict[Future, int] = {}

                def submit(idx: int) -> None:
                    inflight[idx] = inflight.get(idx, 0) + 1
                    fut = ex.submit(work, idx, resident[idx])
                    futmap[fut] = idx
                    pending.add(fut)

                def fill() -> None:
                    nonlocal exhausted, n_seen, n_slots, resident_edges
                    while not exhausted and len(resident) < window:
                        try:
                            chunk = next(it)
                        except StopIteration:
                            exhausted = True
                            break
                        idx = n_seen
                        n_seen += 1
                        n_slots += len(chunk[0])
                        with lock:
                            resident[idx] = chunk
                            resident_edges += len(chunk[0])
                        assert len(resident) <= window
                        self.peak_resident_chunks = max(
                            self.peak_resident_chunks, len(resident)
                        )
                        self.peak_resident_edges = max(
                            self.peak_resident_edges,
                            resident_edges + self._stream_resident_edges,
                        )
                        submit(idx)

                fill()
                if prelude is not None:
                    prelude()
                    prelude_ran = True
                while pending:
                    fin, not_done = wait(pending, return_when=FIRST_COMPLETED)
                    pending = not_done
                    for fut in fin:
                        idx = futmap.pop(fut)
                        err = fut.exception()
                        with lock:
                            inflight[idx] -= 1
                            succeeded = idx in done or idx < reduced
                            live_dup = inflight[idx] > 0
                        if err is not None and not succeeded and not live_dup:
                            if (
                                self.speculative
                                and retries.get(idx, 0) < _MAX_FAILURE_RETRIES
                                and idx in resident
                            ):
                                retries[idx] = retries.get(idx, 0) + 1
                                self.speculative_reissues += 1
                                submit(idx)
                            else:
                                raise err  # the chunk's REAL error
                        if not inflight[idx] and (succeeded or err is None):
                            inflight.pop(idx, None)  # bounded bookkeeping
                            retries.pop(idx, None)
                    # Advance the in-order reduce frontier and refill the
                    # window (reduction overlaps in-flight chunk work; the
                    # O(n) adds run outside the lock).
                    ready = []
                    with lock:
                        while reduced in done:
                            ready.append(done.pop(reduced))
                            chunk = resident.pop(reduced)
                            resident_edges -= len(chunk[0])
                            reduced += 1
                    for dd, tt, cc in ready:
                        deg += dd
                        total += tt
                        n_ok += cc
                    fill()
                    # Back-up tasks for the straggler tail (one round).
                    if (
                        self.speculative
                        and not speculated
                        and exhausted
                        and pending
                        and reduced + len(done)
                        >= (1 - self.speculate_tail_frac) * n_seen
                    ):
                        for idx in list(resident):
                            if idx not in done and inflight.get(idx, 0) > 0:
                                self.speculative_reissues += 1
                                submit(idx)
                        speculated = True
        finally:
            if not prelude_ran:
                prelude()
        return deg, total, n_ok, n_slots

    # ----- geometric compaction (amortized-O(m) streaming) ----------------
    def _compact_stream(
        self,
        stream: Callable[[], Iterator[Chunk]],
        alive_c: np.ndarray,
        id_map: np.ndarray,
        pass_idx: int,
    ):
        """Rebuilds the chunk stream over surviving edges with survivors
        renumbered into a dense (power-of-two padded) node range — one extra
        streaming pass, amortized away by the halved stream it produces.
        Returns ``(stream, alive_c, id_map, n_slots)`` where ``n_slots`` is
        the PADDED slot total of the rebuilt stream — the same quantity the
        next :meth:`_pass_stats` reports and the rung trigger in
        :meth:`run` compares against.

        Memory note: without ``spill_dir`` the rebuilt stream keeps the
        surviving chunks resident in host RAM (never concatenated —
        per-chunk arrays only, so there is no 2x materialization spike);
        the first trigger fires at under half the stream, so residency is
        < m/2 edge slots and halves per rung.  With ``spill_dir`` the
        rebuilt chunks are appended to disk-backed memmaps instead
        (O(chunk) host memory) and the spill — id_map included — is
        published atomically so checkpoint resume can re-enter the ladder
        mid-rung; streams whose SURVIVORS exceed host memory ride the
        ladder this way.  ``residency_cap_edges`` turns a too-large in-RAM
        rebuild into an error instead of a silent memory blow-up.
        """
        from repro.graph.edgelist import EdgeSpillWriter
        from repro.graph.partition import pow2_bucket

        surv = alive_c[: len(id_map)]
        n_alive = int(surv.sum())
        relabel = (np.cumsum(alive_c) - 1).astype(np.int64)
        # Pow2-padded node space (with >= 1 permanently-dead pad node for
        # edge padding below): the jitted chunk kernel sees O(log n)
        # distinct degree-vector shapes across the whole ladder.
        n_pad = pow2_bucket(n_alive + 1, floor=constants.STREAM_REBUILD_NODE_FLOOR)
        pad_id = np.int32(n_pad - 1)  # never alive -> pad edges never count

        spill: Optional[EdgeSpillWriter] = None
        rung_dir: Optional[str] = None
        if self.spill_dir is not None:
            rung_dir = os.path.join(
                self.spill_dir, f"rung_{self.compactions:04d}"
            )
            if os.path.exists(rung_dir):  # stale partial spill from a crash
                shutil.rmtree(rung_dir)
        chunks = []
        caps = []
        n_slots = 0
        w_dtype = None
        try:
            for s, d, w in stream():
                ok = alive_c[s] & alive_c[d]
                kept = int(ok.sum())
                if kept == 0:
                    continue
                # Per-chunk pow2 length so surviving (ragged) chunks land on
                # a bounded set of shapes instead of one compile per chunk.
                cap = pow2_bucket(kept, floor=constants.STREAM_REBUILD_CHUNK_FLOOR)
                cs = np.full(cap, pad_id, np.int32)
                cd = np.full(cap, pad_id, np.int32)
                cw = np.zeros(cap, w.dtype)
                cs[:kept] = relabel[s[ok]]
                cd[:kept] = relabel[d[ok]]
                cw[:kept] = w[ok]
                n_slots += cap
                w_dtype = w.dtype
                if rung_dir is not None:
                    if spill is None:
                        spill = EdgeSpillWriter(rung_dir, w.dtype)
                    spill.append(cs, cd, cw)
                    caps.append(cap)
                else:
                    # The source rung's chunks stay resident while the new
                    # rung accumulates, so the cap (and the peak metric)
                    # covers BOTH — no transient overshoot goes unreported.
                    building = n_slots + self._stream_resident_edges
                    if (
                        self.residency_cap_edges is not None
                        and building > self.residency_cap_edges
                    ):
                        raise RuntimeError(
                            f"compaction rebuild holds {building} edge slots"
                            " in host RAM (source rung + survivors so far),"
                            " exceeding residency_cap_edges="
                            f"{self.residency_cap_edges}; set spill_dir= to"
                            " rebuild the stream on disk instead"
                        )
                    self.peak_resident_edges = max(
                        self.peak_resident_edges, building
                    )
                    chunks.append((cs, cd, cw))
        except BaseException:
            if spill is not None:
                spill.abort()  # close fds + drop the partial rung dir
            raise
        new_alive = np.arange(n_pad) < n_alive
        new_id_map = id_map[surv]

        if rung_dir is not None:
            if spill is None:  # no survivors: publish an empty spill
                spill = EdgeSpillWriter(
                    rung_dir, w_dtype if w_dtype is not None else np.float32
                )
            # repro: allow(fault-sites) spill.finalize fires edgelist.spill_publish inside this try
            try:
                np.save(os.path.join(rung_dir, "id_map.npy"), new_id_map)
                # Publish is atomic (manifest last); a failure here — disk
                # full, injected spill_publish fault — aborts the partial
                # rung so resume can never adopt it, and the error
                # surfaces (the ladder has no stream to continue on).
                spill.finalize(
                    caps=caps,
                    n_pad=int(n_pad),
                    n_alive=int(n_alive),
                    n_nodes=int(self.n_nodes),
                    eps=self.eps,  # guards resume against foreign rungs
                    pass_idx=int(pass_idx),
                    rung=int(self.compactions),
                )
            except BaseException:
                spill.abort()
                raise
            prev = self._cur_rung_dir
            self._cur_rung_dir = rung_dir
            if prev is not None and prev != rung_dir:
                shutil.rmtree(prev, ignore_errors=True)
            gen = _spilled_stream(rung_dir)
            self._stream_resident_edges = 0
            self.spill_rungs += 1
        else:

            def gen() -> Iterator[Chunk]:
                yield from chunks

            self._stream_resident_edges = n_slots
        self.compactions += 1
        return gen, new_alive, new_id_map, n_slots

    def _load_spill(self, st: StreamState):
        """Resume hook: re-enter the ladder on the latest finalized spill
        rung consistent with the checkpoint (the spill was built from an
        alive set at ``manifest.pass_idx <= st.pass_idx``; alive only
        shrinks, so filtering its chunks by the CURRENT alive bitmap is
        exact).  Returns ``(stream, alive_c, id_map)`` or None."""
        from repro.graph.edgelist import open_edge_spill

        if self.spill_dir is None or not os.path.isdir(self.spill_dir):
            return None
        best = None
        for name in sorted(os.listdir(self.spill_dir)):
            rung_dir = os.path.join(self.spill_dir, name)
            if not name.startswith("rung_"):
                continue
            opened = open_edge_spill(rung_dir)
            if opened is None:  # unfinalized (crashed mid-spill): ignore
                continue
            man = opened[3]
            if (
                man.get("n_nodes") != self.n_nodes
                or man.get("eps") != self.eps
                or man.get("pass_idx", 1 << 62) > st.pass_idx
            ):
                continue
            if best is None or man["rung"] > best[1]["rung"]:
                best = (rung_dir, man)
        if best is None:
            return None
        rung_dir, man = best
        id_map = np.load(os.path.join(rung_dir, "id_map.npy"))
        alive_c = np.zeros(man["n_pad"], bool)
        alive_c[: len(id_map)] = st.alive[id_map]
        self.compactions = int(man["rung"]) + 1
        self.spill_rungs = int(man["rung"]) + 1
        self._cur_rung_dir = rung_dir
        return _spilled_stream(rung_dir), alive_c, id_map

    # ----- the algorithm ---------------------------------------------------
    def run(self, max_passes: Optional[int] = None, resume: bool = True) -> StreamState:
        st = self._load() if resume else None
        fresh = st is None
        if fresh:
            st = StreamState(
                alive=np.ones(self.n_nodes, bool),
                best_alive=np.ones(self.n_nodes, bool),
                best_rho=-np.inf,
                pass_idx=0,
            )
        from repro.core.density import max_passes_bound

        if max_passes is None:
            max_passes = max_passes_bound(self.n_nodes, self.eps)

        # Compact view of the live subproblem: ``id_map`` maps compact node
        # ids back to original ids (identity until the first compaction);
        # the FULL-space StreamState is maintained throughout, so the
        # checkpoint format and all outputs are unchanged.
        stream = self.chunk_stream
        id_map = np.arange(self.n_nodes, dtype=np.int64)
        alive_c = st.alive.copy()
        self._stream_resident_edges = 0
        if self.compaction == "geometric" and self.spill_dir is not None:
            if fresh:
                # New lineage: clear rungs of any previous run sharing this
                # spill_dir, so a later resume can never adopt one of them
                # (only the highest rung a run reaches outlives it).
                if os.path.isdir(self.spill_dir):
                    for name in os.listdir(self.spill_dir):
                        if name.startswith("rung_"):
                            shutil.rmtree(
                                os.path.join(self.spill_dir, name),
                                ignore_errors=True,
                            )
            else:
                rec = self._load_spill(st)
                if rec is not None:
                    stream, alive_c, id_map = rec

        step = _pass_step()
        alive_dev = jnp.asarray(alive_c)
        n_cur = int(st.alive.sum())
        pending: Optional[_Deferred] = None
        try:
            while n_cur > 0 and st.pass_idx < max_passes:
                deg, total, e_alive, n_slots = self._pass_stats(
                    alive_dev, stream, prelude=pending
                )
                pending = None
                # The threshold/removal rule is the engine's
                # UndirectedThreshold policy step — the streaming driver only
                # supplies the chunked degree accumulation around it.  The
                # jitted step is dispatched here; everything below that needs
                # only scalars syncs them, and the O(n) host bookkeeping
                # (best-set copy, full-space scatter, checkpoint fsync) is
                # DEFERRED into the next pass's pipeline window.
                new_alive_dev, rho_dev = step(
                    alive_dev, jnp.asarray(deg), np.float32(total), eps=self.eps
                )
                rho = float(rho_dev)
                n_new = int(jnp.count_nonzero(new_alive_dev))

                def fin(
                    st=st,
                    prev_alive=st.alive,
                    n_prev=n_cur,
                    e_alive=e_alive,
                    rho=rho,
                    dev=new_alive_dev,
                    idm=id_map,
                ):
                    st.history.append((n_prev, e_alive, rho))
                    if rho > st.best_rho:
                        st.best_rho = rho
                        st.best_alive = prev_alive.copy()
                    full = np.zeros(self.n_nodes, bool)
                    full[idm] = np.asarray(dev)[: len(idm)]
                    st.alive = full
                    self._save(st)

                st.pass_idx += 1
                pending = _Deferred(fin)
                alive_dev = new_alive_dev
                n_cur = n_new
                if (
                    self.compaction == "geometric"
                    and n_cur > 0
                    and st.pass_idx < max_passes  # a rebuild needs a consumer
                    and 2 * e_alive < n_slots
                ):
                    pending()  # the rebuild reads a settled checkpoint state
                    pending = None
                    alive_c = np.asarray(alive_dev)
                    stream, alive_c, id_map, n_slots = self._compact_stream(
                        stream, alive_c, id_map, st.pass_idx
                    )
                    alive_dev = jnp.asarray(alive_c)
        finally:
            if pending is not None:
                pending()
        return st


def _spilled_stream(rung_dir: str) -> Callable[[], Iterator[Chunk]]:
    """Chunk-stream factory over a finalized spill rung: each chunk is a
    memmap slice, read from disk on demand (O(chunk) host residency)."""
    from repro.graph.edgelist import open_edge_spill

    def gen() -> Iterator[Chunk]:
        opened = open_edge_spill(rung_dir)
        if opened is None:
            raise FileNotFoundError(f"no finalized edge spill in {rung_dir}")
        src, dst, w, man = opened
        off = 0
        for cap in man["caps"]:
            yield src[off : off + cap], dst[off : off + cap], w[off : off + cap]
            off += cap

    return gen


def chunked_from_arrays(
    src: np.ndarray, dst: np.ndarray, w: Optional[np.ndarray], chunk: int
) -> Callable[[], Iterator[Chunk]]:
    """Chunk-stream factory over in-memory / memmapped edge arrays."""
    if w is None:
        w = np.ones_like(src, np.float32)

    def gen() -> Iterator[Chunk]:
        for lo in range(0, len(src), chunk):
            hi = min(lo + chunk, len(src))
            yield src[lo:hi], dst[lo:hi], w[lo:hi]

    return gen


def chunked_from_memmap(
    store_dir: str, chunk: int
) -> Callable[[], Iterator[Chunk]]:
    """Chunk-stream factory over an on-disk edge store written by
    :func:`repro.graph.edgelist.save_edges_memmap`: the edges never enter
    host RAM whole — each chunk is a memmap slice read on demand, so the
    stream's home is the disk, as §4's model intends."""
    from repro.graph.edgelist import open_edges_memmap

    def gen() -> Iterator[Chunk]:
        src, dst, w = open_edges_memmap(store_dir)
        for lo in range(0, len(src), chunk):
            hi = min(lo + chunk, len(src))
            yield src[lo:hi], dst[lo:hi], w[lo:hi]

    return gen
