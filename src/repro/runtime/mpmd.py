"""MPMD multi-controller executor: one traced program PER RANK.

The SPMD executor (``runtime/spmd.py``) traces every rank's chunks into
ONE whole-mesh ``jax.jit`` program and gates per-rank compute with
``lax.cond`` — correct and bit-verified, but each device carries the
entire mesh's trace and all communication lowers to SPMD collectives
(``lax.ppermute`` / ``lax.psum``) inside a single dispatch.  This module
is the multi-controller alternative the ROADMAP's top open item asks
for, following JaxPP's MPMD pipeline-parallel design (PAPERS.md,
arxiv 2412.14374): ``GlobalPlan.rank_program(r)`` compiles into a
*per-rank* ``jax.jit`` program containing ONLY rank r's chunks — no
``lax.cond`` gating, no whole-mesh trace (``trace_sizes()`` vs
``SpmdExecutor.trace_size()`` quantifies the shrink) — and N controller
threads dispatch the N programs concurrently, communicating through a
real asynchronous message transport instead of XLA collectives.

IR-op -> transport lowering (DESIGN.md §17 has the full table, the MPMD
mirror of §12's SPMD table):

  chunk                 traced unconditionally (only members carry the
                        task); feeds/params resolved per rank
  p2p send              ordered ``io_callback`` posting the payload on
                        the tagged channel (node, src, dst)
  p2p recv              ordered ``io_callback`` blocking on that channel
                        and dynamically type-checking the payload
                        against the receiver's wired ``ValueSpec``
  all_gather (param)    the rank's 1/|group| shard of the bucket's
                        params, bit-cast to unsigned words per width,
                        goes through a subgroup rendezvous; the callback
                        returns the full word vector, rebuilt in-trace
                        into the gathered tree
                        the consuming chunks read (load-bearing, exactly
                        like the SPMD lowering)
  all_reduce /          every member posts its locally accumulated
  reduce_scatter (grad) (tree, count) to the subgroup rendezvous; the
                        group's lowest rank folds contributions in the
                        interpreter's own advance order with the
                        reference formula ``sum(x/c)/n`` and hands the
                        mean to the controller epilogue
  all_to_all (EP)       rendezvous round trip: each member's block
                        crosses the transport and returns (identity
                        values, real dispatch + return bytes — the
                        reference runtime models EP math shard-locally)
  d2h / h2d (Offload)   rank-local ``lax.optimization_barrier`` identity
                        (same documented fallback as SPMD)

Startup handshake (the PIPER025 gate, cashed in): before any program
runs, every rank serializes its typed interface signature
(``GlobalPlan.rank_signature`` — sends/recvs/collectives in dispatch
order) and exchanges it with all peers over the transport; each rank
then pairwise-validates every p2p channel and collective group it is
party to, exactly the agreement ``analysis.rank_interface_diagnostics``
checks statically.  A mismatch raises ``MpmdHandshakeError`` naming both
ranks — the executor refuses to start rather than desync at runtime
(``signature_overrides=`` is the fault-injection seam the negative-path
test corrupts).

Transports (one ``_Board`` semantics, two wire shapes):

  ``transport="inproc"``  threads + queues + condition-variable
                          rendezvous in-process (the CI default on N
                          host-faked devices);
  ``transport="tcp"``     the same board behind a localhost TCP server —
                          every send/recv/rendezvous serializes its
                          payload over a real socket (process-shaped
                          wire realism).

  True subprocess-per-rank is not possible here: ``Node.fn`` chunk
  closures capture traced model callables that do not pickle.  The
  controller therefore drives N threads — but each rank's program is
  its own jit executable on its own XLA device, every cross-rank byte
  moves through the transport, and nothing in the executor assumes
  shared memory beyond the transport API, so swapping in a socket
  transport per real host is a deployment change, not a redesign.

Bit-parity with the reference interpreter is by construction, the same
argument as SPMD: each rank's compute/collective trace order IS the
interpreter's dynamic dispatch order restricted to that rank
(``replay_schedule``, including the FSDP-style gather rate limiter),
gradient reductions fold in the interpreter's own member order with its
exact formula, and the controller epilogue applies the reference
loss/grad reductions in ``ScheduleReplay`` order
(tests/test_mpmd_executor.py: fp64 bit-parity on the
{1f1b,gpipe,dualpipev} x ZeRO{0,3} grid).

One wrinkle the raw replay projection hides: the interpreter consumes
p2p VALUES straight from the producer's store, so its global order can
legally run a recv *task* before the matching send task — fine for a
sequential simulator, a deadlock for real blocking transports (rank A
blocks in the recv, never reaching the collective post rank B needs
before it can send; XLA's CPU runtime executes a rank program's
callbacks strictly sequentially, so a blocking callback blocks the
whole rank).  ``_rank_orders`` therefore re-derives each rank's trace
order by replaying the plan's task graph under *real* transport
semantics — sends complete once their producer ran (non-blocking
post), a recv completes only after its send task, rendezvous
collectives complete atomically when every member arrives — while
pinning every compute/collective to its replay-projection position.
The construction sequence is itself a feasible global interleaving
(a witness), so the per-rank blocking execution it projects to cannot
deadlock; and because only send/recv tasks move (neither touches
gradient accumulation or reduction state), bit-parity is untouched.

A plan that fails ``validate_comm_order`` is rejected at construction,
before tracing; a rank that stalls at runtime trips the transport
timeout and poisons all peers (``MpmdTransportError`` — the dynamic
analogue of the PIPER001 deadlock the static verifier rejects).
"""
from __future__ import annotations

import json
import pickle
import socket
import struct
import threading
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import io_callback

from ..core.compiler import CompiledProgram
from ..core.plan import ROLE_COLL, ROLE_COMPUTE, ROLE_RECV, ROLE_SEND
from ..core.scheduler import validate_comm_order
from .executor import jaxpr_eqn_count, register_backend
from .interpreter import RunResult, ScheduleReplay, _PlanWalker
from .spmd import _concat_words, _split_words, gather_chunk_args

tree_map = jax.tree_util.tree_map


class MpmdBackendError(RuntimeError):
    """The MPMD executor cannot run this plan on the available devices."""


class MpmdHandshakeError(MpmdBackendError):
    """The startup signature handshake found peers whose typed
    interfaces disagree (the dynamic PIPER025) — the executor refuses
    to start."""


class MpmdTransportError(RuntimeError):
    """A transport operation timed out or was poisoned by a failing
    peer — the dynamic analogue of the PIPER001 deadlock the static
    verifier rejects."""


# ---------------------------------------------------------------------------
# message board: tagged channels + keyed rendezvous
# ---------------------------------------------------------------------------

class _Board:
    """The one message-passing semantics both transports implement:
    FIFO channels keyed by tag (p2p) and all-post/all-fetch rendezvous
    slots keyed by op instance (collectives).  ``abort`` poisons every
    current and future waiter so one failing rank cannot strand its
    peers at a rendezvous."""

    def __init__(self) -> None:
        self._cv = threading.Condition()
        self._chan: dict[tuple, deque] = {}
        self._rdv: dict[tuple, dict] = {}
        self._poison: Optional[str] = None

    def _check(self) -> None:
        if self._poison is not None:
            raise MpmdTransportError(
                f"transport poisoned: {self._poison}")

    def reset(self) -> None:
        with self._cv:
            self._chan.clear()
            self._rdv.clear()
            self._poison = None
            self._cv.notify_all()

    def abort(self, msg: str) -> None:
        with self._cv:
            if self._poison is None:
                self._poison = msg
            self._cv.notify_all()

    def send(self, tag: tuple, payload) -> None:
        with self._cv:
            self._check()
            self._chan.setdefault(tag, deque()).append(payload)
            self._cv.notify_all()

    def recv(self, tag: tuple, timeout: float):
        deadline = time.monotonic() + timeout
        with self._cv:
            while True:
                self._check()
                q = self._chan.get(tag)
                if q:
                    return q.popleft()
                left = deadline - time.monotonic()
                if left <= 0 or not self._cv.wait(timeout=left):
                    raise MpmdTransportError(
                        f"recv on channel {tag} timed out after "
                        f"{timeout:.0f}s — peer never sent (the dynamic "
                        "analogue of a PIPER001 desync)")

    def gather(self, key: tuple, pos: int, nposts: int, payload,
               timeout: float) -> list:
        """Rendezvous allgather: post as member ``pos`` of ``nposts``,
        block until all members posted, return payloads in pos order.
        The last fetcher retires the slot."""
        deadline = time.monotonic() + timeout
        with self._cv:
            self._check()
            slot = self._rdv.setdefault(key, {"posts": {}, "taken": 0})
            slot["posts"][pos] = payload
            self._cv.notify_all()
            while len(slot["posts"]) < nposts:
                self._check()
                left = deadline - time.monotonic()
                if left <= 0 or not self._cv.wait(timeout=left):
                    missing = sorted(set(range(nposts))
                                     - set(slot["posts"]))
                    raise MpmdTransportError(
                        f"rendezvous {key} timed out after "
                        f"{timeout:.0f}s waiting for member(s) "
                        f"{missing} of {nposts}")
            out = [slot["posts"][p] for p in sorted(slot["posts"])]
            slot["taken"] += 1
            if slot["taken"] >= nposts:
                self._rdv.pop(key, None)
            return out


class InprocTransport:
    """Threads sharing one in-process board — the CI default.  All
    payloads still flow through the board (no rank reads another's
    store); only the wire is a queue instead of a socket."""
    name = "inproc"

    def __init__(self) -> None:
        self._board = _Board()

    def reset(self) -> None:
        self._board.reset()

    def abort(self, msg: str) -> None:
        self._board.abort(msg)

    def send(self, tag, payload) -> None:
        self._board.send(tag, payload)

    def recv(self, tag, timeout):
        return self._board.recv(tag, timeout)

    def gather(self, key, pos, nposts, payload, timeout):
        return self._board.gather(key, pos, nposts, payload, timeout)

    def close(self) -> None:
        pass


class TcpTransport:
    """The same board behind a localhost TCP server: every operation is
    a length-prefixed pickled request over a fresh socket, so every
    cross-rank payload crosses a real OS socket (process-shaped wire
    realism; blocking ops block their server-side connection thread).
    """
    name = "tcp"

    def __init__(self) -> None:
        self._board = _Board()
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind(("127.0.0.1", 0))
        self._srv.listen(128)
        self.address = self._srv.getsockname()
        self._closing = False
        self._accept_thread = threading.Thread(
            target=self._accept_loop, name="mpmd-tcp-accept", daemon=True)
        self._accept_thread.start()

    # -- framing ---------------------------------------------------------
    @staticmethod
    def _send_msg(sock, obj) -> None:
        data = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
        sock.sendall(struct.pack(">Q", len(data)) + data)

    @staticmethod
    def _recv_msg(sock):
        hdr = b""
        while len(hdr) < 8:
            part = sock.recv(8 - len(hdr))
            if not part:
                raise ConnectionError("peer closed")
            hdr += part
        (n,) = struct.unpack(">Q", hdr)
        buf = bytearray()
        while len(buf) < n:
            part = sock.recv(min(1 << 20, n - len(buf)))
            if not part:
                raise ConnectionError("peer closed")
            buf += part
        return pickle.loads(bytes(buf))

    # -- server ----------------------------------------------------------
    def _accept_loop(self) -> None:
        while not self._closing:
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(target=self._serve_one, args=(conn,),
                             daemon=True).start()

    def _serve_one(self, conn) -> None:
        try:
            with conn:
                op, args = self._recv_msg(conn)
                try:
                    result = getattr(self._board, op)(*args)
                    self._send_msg(conn, (True, result))
                except Exception as e:  # marshalled to the client
                    self._send_msg(conn, (False, f"{type(e).__name__}: {e}"))
        except (ConnectionError, OSError):
            pass

    # -- client ----------------------------------------------------------
    def _call(self, op: str, *args):
        with socket.create_connection(self.address, timeout=600) as sock:
            self._send_msg(sock, (op, args))
            ok, result = self._recv_msg(sock)
        if not ok:
            raise MpmdTransportError(result)
        return result

    def reset(self) -> None:
        self._call("reset")

    def abort(self, msg: str) -> None:
        self._call("abort", msg)

    def send(self, tag, payload) -> None:
        self._call("send", tag, payload)

    def recv(self, tag, timeout):
        return self._call("recv", tag, timeout)

    def gather(self, key, pos, nposts, payload, timeout):
        return self._call("gather", key, pos, nposts, payload, timeout)

    def close(self) -> None:
        self._closing = True
        try:
            self._srv.close()
        except OSError:
            pass


_TRANSPORTS = {"inproc": InprocTransport, "tcp": TcpTransport}


def _ensure_sync_cpu_dispatch() -> None:
    """Force synchronous CPU dispatch before any rank program runs.

    jax's CPU async dispatch executes programs on a small client-wide
    worker pool.  A rank parked inside a blocking transport callback
    (recv / rendezvous) parks one of those workers, and once every
    worker is parked the remaining rank programs never START — a
    starvation deadlock the per-rank order witness cannot see, because
    it is not an ordering problem (observed on a 4-rank ZeRO-3 run:
    the starved ranks reached their first transport op exactly when a
    parked peer timed out and freed its worker).  Synchronous dispatch
    runs each rank's program — and its blocking callbacks — on its own
    controller thread, which is the multi-controller model anyway.

    The flag is consumed at CPU *client creation*
    (``xla_bridge.make_cpu_client(asynchronous=...)``), so flipping the
    config after first jax use is a no-op; if an async client already
    exists it must be rebuilt.  Old arrays stay readable (np.asarray
    re-transfers), but device handles captured before the rebuild go
    stale — hence this runs before ``__init__`` touches
    ``jax.devices()``.  On any other platform it does nothing: clearing
    the backends there would drop the accelerator's client and every
    array already placed on it.
    """
    if not jax.config.read("jax_cpu_enable_async_dispatch") \
            or jax.default_backend() != "cpu":
        return
    jax.config.update("jax_cpu_enable_async_dispatch", False)
    import jax.extend.backend as _jeb
    _jeb.clear_backends()


# ---------------------------------------------------------------------------
# rank-signature serialization (the handshake payload)
# ---------------------------------------------------------------------------

def serialize_rank_signature(sig: dict) -> bytes:
    """Deterministic wire form of ``GlobalPlan.rank_signature``: specs
    as stable reprs, groups as lists — byte-comparable and corruptible
    (the ``signature_overrides`` test seam)."""
    return json.dumps({
        "device": sig["device"],
        "sends": [[p, n, repr(s)] for (p, n, s) in sig["sends"]],
        "recvs": [[p, n, repr(s)] for (p, n, s) in sig["recvs"]],
        "collectives": [[list(g), n, op, payload, [repr(s) for s in specs]]
                        for (g, n, op, payload, specs)
                        in sig["collectives"]],
    }, sort_keys=True).encode()


def _pairwise_errors(r: int, mine: dict, peers: dict[int, dict]) -> list[str]:
    """Rank r's view of the PIPER025 pairwise agreement: every p2p
    channel r is party to, both directions, and every collective group
    containing r — mirroring ``analysis.rank_interface_diagnostics``."""
    errs: list[str] = []

    def chan_seqs(src_sig, dst_sig, src, dst):
        s_seq = [(n, sp) for (p, n, sp) in src_sig["sends"] if p == dst]
        r_seq = [(n, sp) for (p, n, sp) in dst_sig["recvs"] if p == src]
        return s_seq, r_seq

    out_peers = {p for (p, _, _) in mine["sends"]}
    in_peers = {p for (p, _, _) in mine["recvs"]}
    for p in sorted(out_peers | in_peers):
        if p not in peers:
            errs.append(f"[PIPER025] rank {r} names rank {p} in its "
                        "interface but no such rank joined the handshake")
            continue
        for (src, dst), (src_sig, dst_sig) in (
                ((r, p), (mine, peers[p])), ((p, r), (peers[p], mine))):
            s_seq, r_seq = chan_seqs(src_sig, dst_sig, src, dst)
            if len(s_seq) != len(r_seq):
                errs.append(
                    f"[PIPER025] rank {src} sends {len(s_seq)} p2p "
                    f"payload(s) to rank {dst} but rank {dst}'s program "
                    f"expects {len(r_seq)} — the per-rank programs "
                    "would desync")
                continue
            for i, ((snid, ss), (rnid, rs)) in enumerate(
                    zip(s_seq, r_seq)):
                if ss != rs and "None" not in (ss, rs):
                    errs.append(
                        f"[PIPER025] p2p interface mismatch on channel "
                        f"rank {src} -> rank {dst} at position {i} "
                        f"(nodes {snid}/{rnid}): the sender supplies "
                        f"{ss} but the receiver was wired for {rs}")

    groups = {tuple(g) for (g, *_rest) in mine["collectives"]}
    for g in sorted(groups):
        ref = [c[1:] for c in mine["collectives"] if tuple(c[0]) == g]
        for m in g:
            if m == r:
                continue
            if m not in peers:
                errs.append(f"[PIPER025] collective group {list(g)} "
                            f"names rank {m} but it never joined the "
                            "handshake")
                continue
            seq = [c[1:] for c in peers[m]["collectives"]
                   if tuple(c[0]) == g]
            if seq == ref:
                continue
            pos = next((i for i, (a, b) in enumerate(zip(ref, seq))
                        if a != b), min(len(ref), len(seq)))
            errs.append(
                f"[PIPER025] collective signature of group {list(g)} "
                f"diverges between rank {r} ({len(ref)} dispatches) "
                f"and rank {m} ({len(seq)} dispatches) at position "
                f"{pos} — an MPMD rendezvous would hang or corrupt")
    return errs


# ---------------------------------------------------------------------------
# wire-shape oracle
# ---------------------------------------------------------------------------

class _ShapeOracle(_PlanWalker):
    """Device-aware abstract interpretation of one batch signature.

    IR ``ValueSpec``s are *logical* shapes — a DP-replicated producer
    declares ``(mb, d)`` while each device actually emits its
    ``(mb/dp, d)`` shard — so a receiver cannot learn its wire shape
    from the edge spec alone.  This pass walks the interpreter's own
    dispatch loop (it IS the ``_PlanWalker`` replay, so the executor
    gets the ``ScheduleReplay`` and the shapes from ONE walk) with
    chunk execution replaced by ``jax.eval_shape``, propagating
    per-device avals through every store move and recording, for each
    p2p recv, the concrete (shape, dtype) that crosses that channel —
    the receiver-side contract ``MpmdExecutor._trace_recv`` traces
    against and dynamically re-checks on every arriving payload."""

    def __init__(self, prog: CompiledProgram,
                 gather_limit: Optional[int] = None) -> None:
        super().__init__(prog, gather_limit=gather_limit)
        self.p2p_shapes: dict[tuple[int, int], tuple] = {}

    def replay(self, batch: dict[str, Any]) -> ScheduleReplay:
        self.p2p_shapes = {}
        return super().replay(batch)

    def _aval_args(self, node, t, store, feeds):
        # _gather_chunk_inputs, aval-safe: multi-source cotangent slots
        # share one shape, so the summed aval is its first contributor
        m = node.meta.get("n_inputs", 0)
        args: list = []
        for slot in range(m):
            key = (node.id, slot, t.device)
            if key in feeds:
                args.append(feeds[key])
                continue
            vals = [store[(e.src, e.src_out, t.device)]
                    for e in self.dag.in_edges(node.id)
                    if e.dst_in == slot
                    and (e.src, e.src_out, t.device) in store]
            args.append(vals[0] if vals else None)
        if "fwd_node" in node.meta:
            fwd = self.dag.nodes[node.meta["fwd_node"]]
            n_cots = node.meta.get("n_cots", fwd.n_outputs)
            m0 = node.meta["n_inputs"] - n_cots
            for slot in (list(node.meta.get("seed_slots", []))
                         + list(node.meta.get("zero_cot_slots", []))):
                s = fwd.out_specs[slot - m0]
                args[slot] = jax.ShapeDtypeStruct(tuple(s.shape),
                                                  np.dtype(s.dtype))
        return args

    def _exec_chunk(self, node, t, store, feeds, cons, grad_acc, grad_cnt,
                    losses, ledgers, gather_left, gather_consumers) -> None:
        args = self._aval_args(node, t, store, feeds)
        bp = self.params.get(node.bucket) if node.bucket else None
        outs = jax.eval_shape(lambda p, a: node.fn(p, *a), bp, tuple(args))
        if node.meta.get("is_backward", False):
            out_vals = list(outs[1:])
            out_slots = list(range(1, len(outs)))
        else:
            out_vals = list(outs)
            out_slots = list(range(len(outs)))
        discard = set(node.meta.get("discard_out_slots", []))
        for slot, val in zip(out_slots, out_vals):
            if slot in discard or val is None:
                continue
            key = (node.id, slot, t.device)
            if cons.get(key):
                store[key] = val
        self._release_inputs(node, t, store, cons, ledgers)
        super()._exec_chunk(node, t, store, feeds, cons, grad_acc,
                            grad_cnt, losses, ledgers, gather_left,
                            gather_consumers)

    def _exec_recv(self, node, t, store, cons, ledgers) -> None:
        e = self.dag.in_edges(node.id)[0]
        src_dev = None
        for (s, d) in node.meta["pairs"]:
            if d == t.device:
                src_dev = s
        val = store.get((e.src, e.src_out, src_dev))
        if val is not None:
            store[(node.id, 0, t.device)] = val
            self.p2p_shapes[(node.id, t.device)] = (
                tuple(val.shape), np.dtype(val.dtype))
            pkey = (e.src, e.src_out, src_dev)
            cons[pkey] = cons.get(pkey, 1) - 1
            if cons[pkey] <= 0:
                store.pop(pkey, None)

    def _exec_collective(self, node, group_tasks, store, grad_acc,
                         grad_cnt, reduced, reduced_cnt, ledgers, cons,
                         gather_left) -> None:
        # keep the walker's rate-limiter/reduction bookkeeping, but also
        # move avals through pass-through ops so downstream chunks on
        # the same device can assemble their inputs
        if node.op in ("d2h", "h2d", "all_to_all", "broadcast") \
                or (node.op not in ("all_gather",)
                    and node.payload != "grad"):
            for t in group_tasks:
                for e in self.dag.in_edges(node.id):
                    v = store.get((e.src, e.src_out, t.device))
                    if v is not None:
                        store[(node.id, 0, t.device)] = v
            for t in group_tasks:
                self._release_inputs(node, t, store, cons, ledgers)
        super()._exec_collective(node, group_tasks, store, grad_acc,
                                 grad_cnt, reduced, reduced_cnt, ledgers,
                                 cons, gather_left)


# ---------------------------------------------------------------------------
# the executor
# ---------------------------------------------------------------------------

@dataclass
class _Built:
    """Per-batch-signature build: one traced+jitted program per rank,
    plus the replayed schedule facts the controller epilogue reads."""
    replay: ScheduleReplay
    traced: dict[int, Any] = field(default_factory=dict)
    fns: dict[int, Any] = field(default_factory=dict)
    compiled: dict[int, Any] = field(default_factory=dict)
    reduce_fold: dict[int, list[int]] = field(default_factory=dict)
    acc_cnt: dict[tuple[str, int], int] = field(default_factory=dict)
    p2p_shapes: dict[tuple[int, int], tuple] = field(default_factory=dict)
    n_tasks: int = 0


@register_backend("mpmd")
class MpmdExecutor:
    """Execute a ``CompiledProgram`` as N per-rank jit programs driven
    by N controller threads over an async message transport.

    ``transport``: "inproc" (default) or "tcp" (localhost sockets).
    ``timeout``: seconds any single transport wait may block before the
    run is declared desynced.
    ``signature_overrides``: {rank: signature-dict-or-bytes} replacing
    that rank's handshake payload — the fault-injection seam.
    ``handshake=False`` skips the startup signature exchange (only for
    harnesses that measure its cost separately).
    """

    def __init__(self, prog: CompiledProgram,
                 params: Optional[dict[str, Any]] = None, *,
                 transport: str = "inproc",
                 gather_limit: Optional[int] = None,
                 physical_devices: Optional[Sequence[int]] = None,
                 timeout: float = 60.0,
                 signature_overrides: Optional[dict] = None,
                 handshake: bool = True) -> None:
        # static rejection BEFORE any thread or trace exists — the
        # dynamic analogue is a rendezvous deadlock across controllers
        validate_comm_order(prog.dag, prog.plan)
        # must precede the jax.devices() capture below: rebuilding the
        # CPU client invalidates previously captured device handles
        _ensure_sync_cpu_dispatch()
        self.prog = prog
        self.dag = prog.dag
        self.plan = prog.plan
        self.params = params if params is not None else prog.params
        self.timeout = float(timeout)
        self.devices = sorted(self.plan.devices)
        self.n = len(self.devices)
        if transport not in _TRANSPORTS:
            raise MpmdBackendError(
                f"unknown transport {transport!r}; available: "
                f"{sorted(_TRANSPORTS)}")
        self.transport = _TRANSPORTS[transport]()
        avail = jax.devices()
        if physical_devices is not None:
            # elastic recovery contract (same rules as SpmdExecutor):
            # the n logical ranks land on exactly these distinct
            # jax.devices() indices, so a shrunk/regrown world never
            # touches a failed chip
            phys = [int(p) for p in physical_devices]
            if len(phys) != self.n:
                raise MpmdBackendError(
                    f"plan spans {self.n} devices but physical_devices "
                    f"names {len(phys)}: {phys}")
            bad = [p for p in phys if not 0 <= p < len(avail)]
            if bad or len(set(phys)) != len(phys):
                raise MpmdBackendError(
                    f"physical_devices must be {len(phys)} distinct "
                    f"indices into jax.devices() (0..{len(avail)-1}), "
                    f"got {phys}")
            chosen = [avail[p] for p in phys]
        else:
            # unlike SPMD (one shard_map over n mesh devices), rank
            # programs are independent executables — on the CPU,
            # oversubscribing fewer devices is allowed (rank r -> device
            # r mod D), which is what lets world-4 smoke tests run on 1
            # CPU device.  An accelerator runs one program at a time, so
            # two ranks whose callbacks wait on each other would hang on
            # one chip
            platform = avail[0].platform
            if platform != "cpu" and len(avail) < self.n:
                raise MpmdBackendError(
                    f"plan spans {self.n} ranks but platform {platform} "
                    f"has {len(avail)} device(s) "
                    f"({avail[0].device_kind}); the MPMD backend needs "
                    "one device per rank off the CPU")
            chosen = [avail[i % len(avail)] for i in range(self.n)]
        self.physical_devices = tuple(
            d.id if hasattr(d, "id") else i for i, d in enumerate(chosen))
        self._devmap = {d: chosen[i] for i, d in enumerate(self.devices)}
        self._resolver = _ShapeOracle(prog, gather_limit=gather_limit)
        self._built: dict[tuple, _Built] = {}
        self._gen = 0
        self._events: list[tuple[str, bool, Any]] = []
        self._events_lock = threading.Lock()
        if handshake:
            self._handshake(signature_overrides or {})

    # ------------------------------------------------------------ handshake
    def _handshake(self, overrides: dict) -> None:
        raw: dict[int, bytes] = {}
        for r in self.devices:
            o = overrides.get(r)
            if o is None:
                raw[r] = serialize_rank_signature(
                    self.plan.rank_signature(r, self.dag))
            else:
                raw[r] = o if isinstance(o, bytes) \
                    else serialize_rank_signature(o)
        errors: list[str] = []
        lock = threading.Lock()

        def worker(pos: int, r: int) -> None:
            try:
                posts = self.transport.gather(
                    ("handshake", self._gen), pos, self.n,
                    (r, raw[r]), self.timeout)
                sigs = {d: json.loads(b) for (d, b) in posts}
                errs = _pairwise_errors(r, sigs[r], sigs)
                if errs:
                    with lock:
                        errors.extend(errs)
            except MpmdTransportError as e:
                with lock:
                    errors.append(f"[PIPER025] rank {r}: {e}")
                self.transport.abort(f"handshake failed on rank {r}")

        threads = [threading.Thread(target=worker, args=(i, r),
                                    name=f"mpmd-hs{r}")
                   for i, r in enumerate(self.devices)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(self.timeout + 5)
        self.transport.reset()
        if errors:
            uniq = sorted(set(errors))
            raise MpmdHandshakeError(
                "MPMD startup handshake failed — peer rank signatures "
                "disagree (PIPER025):\n  " + "\n  ".join(uniq[:8]))

    # ------------------------------------------------------------ helpers
    def _sig(self, batch) -> tuple:
        return tuple(sorted(
            (k, tuple(np.shape(v)),
             str(getattr(v, "dtype", None) or np.asarray(v).dtype))
            for k, v in batch.items()))

    def _rank_feeds(self, batch) -> dict[int, dict[tuple, Any]]:
        feeds3 = self._resolver._resolve_inputs(batch)
        out: dict[int, dict[tuple, Any]] = {r: {} for r in self.devices}
        for (nid, slot, d), v in feeds3.items():
            out[d][(nid, slot)] = np.asarray(v)
        return out

    # ------------------------------------------------------------ build
    def _ensure_built(self, batch) -> _Built:
        key = self._sig(batch)
        if key not in self._built:
            self._built[key] = self._build(batch)
        return self._built[key]

    def _build(self, batch) -> _Built:
        replay = self._resolver.replay(batch)
        b = _Built(replay=replay,
                   p2p_shapes=dict(self._resolver.p2p_shapes),
                   n_tasks=sum(p.n_tasks()
                               for p in self.plan.device_plans.values()))
        # grad-reduce fold order: the interpreter advances a collective's
        # group tasks consecutively ([t] + peers), so the run of same-nid
        # ROLE_COLL entries in exec_order IS its member fold order
        grad_nids = {n.id for n in self.dag.nodes.values()
                     if n.is_comm and n.payload == "grad"
                     and n.op in ("all_reduce", "reduce_scatter")}
        for (nid, dev, role) in replay.exec_order:
            if role == ROLE_COLL and nid in grad_nids:
                b.reduce_fold.setdefault(nid, []).append(dev)
        orders = self._rank_orders(replay)
        for r in self.devices:
            traced = self._make_traced(r, orders[r], b)
            b.traced[r] = traced
            b.fns[r] = jax.jit(traced)
        return b

    def _rank_orders(self, replay) -> dict[int, list[tuple[int, str]]]:
        """Deadlock-free per-rank trace orders (module docstring: the
        witness construction).  Greedy completion over the plan's task
        graph in replay order, under blocking-transport semantics:

          compute/coll   pinned to the replay projection — each waits
                         for its rank's previous compute/coll, so the
                         numerics-bearing order is exactly the
                         interpreter's
          send           completes once its ``Task.deps`` (the producer
                         chunk) ran — a non-blocking post may float
                         ahead of its replay slot
          recv           completes only after its paired send task
                         (``Task.deps`` already contains it)
          rendezvous     all members complete atomically, each member's
                         own prerequisites permitting

        The completion sequence is a feasible global interleaving, so
        its per-rank projections cannot deadlock when each rank runs
        them as one blocking ordered-callback chain."""
        keys = [k for k in replay.exec_order]
        tasks = {}
        for p in self.plan.device_plans.values():
            tasks.update(p.tasks)
        # pinned chain: non-p2p tasks in per-rank projection order
        pinned: dict[tuple, tuple] = {}
        last: dict[int, tuple] = {}
        for k in keys:
            (nid, dev, role) = k
            if role in (ROLE_SEND, ROLE_RECV):
                continue
            if dev in last:
                pinned[k] = last[dev]
            last[dev] = k
        done: set[tuple] = set()
        pending = dict.fromkeys(keys)   # insertion-ordered set
        out: dict[int, list[tuple[int, str]]] = {
            r: [] for r in self.devices}

        def arrived(k) -> bool:
            t = tasks.get(k)
            peers = set(t.peers) if t is not None else set()
            if t is not None and any(d not in done for d in t.deps
                                     if d not in peers):
                return False
            return pinned.get(k) is None or pinned[k] in done

        def solo_ready(k) -> bool:
            t = tasks.get(k)
            if t is not None and any(d not in done for d in t.deps):
                return False
            return pinned.get(k) is None or pinned[k] in done

        def finish(k) -> None:
            done.add(k)
            pending.pop(k, None)
            out[k[1]].append((k[0], k[2]))

        while pending:
            progressed = False
            for k in list(pending):
                role = k[2]
                if role == ROLE_COLL:
                    t = tasks.get(k)
                    cohort = [k] + [p for p in (t.peers if t else [])
                                    if p in pending]
                    if all(arrived(m) for m in cohort):
                        for m in cohort:
                            finish(m)
                        progressed = True
                elif solo_ready(k):
                    finish(k)
                    progressed = True
                if progressed:
                    break
            if not progressed:
                stuck = ", ".join(map(str, list(pending)[:6]))
                raise MpmdBackendError(
                    "no feasible blocking execution of this plan — "
                    f"{len(pending)} task(s) unreachable under "
                    f"transport semantics (first: {stuck}); the static "
                    "verifier should have rejected this schedule "
                    "(PIPER001)")
        return out

    # ------------------------------------------------------------ tracing
    def _make_traced(self, r: int, order: list[tuple[int, str]],
                     built: _Built):
        dag = self.dag

        def traced(prm, feeds):
            store: dict[tuple[int, int], Any] = {}
            gathered: dict[int, dict[str, Any]] = {}
            grad_acc: dict[str, Any] = {}
            grad_cnt: dict[str, int] = {}
            loss_vals: dict[tuple[int, int], Any] = {}
            toks: list[Any] = []
            for (nid, role) in order:
                node = dag.nodes[nid]
                if role == ROLE_COMPUTE:
                    self._trace_chunk(r, node, prm, feeds, store,
                                      gathered, grad_acc, grad_cnt,
                                      loss_vals)
                elif role == ROLE_SEND:
                    self._trace_send(r, node, store, toks)
                elif role == ROLE_RECV:
                    self._trace_recv(r, node, store, built)
                elif node.op == "all_gather" and node.payload == "param":
                    self._trace_param_gather(r, node, prm, gathered)
                elif node.op in ("all_reduce", "reduce_scatter") \
                        and node.payload == "grad":
                    self._trace_grad_reduce(r, node, grad_acc, grad_cnt,
                                            built, toks)
                elif node.op == "all_to_all":
                    self._trace_a2a(r, node, store)
                elif node.op in ("d2h", "h2d"):
                    self._trace_passthrough(node, store, barrier=True)
                else:  # broadcast / generic activation collective
                    self._trace_passthrough(node, store, barrier=False)
            for bkt, cnt in grad_cnt.items():   # never-reduced buckets
                built.acc_cnt[(bkt, r)] = cnt
            # completion fence: block_until_ready on the outputs only
            # waits for the OUTPUT buffers — a trailing callback whose
            # result is otherwise unused (a send, an owner-side reduce)
            # may still be in flight when the controller snapshots the
            # event log.  Every send/reduce callback returns a uint8
            # token; folding them into an output makes each callback's
            # completion a data dependency of the step result.
            fence = jnp.zeros((), jnp.uint8)
            for t in toks:
                fence = jnp.bitwise_or(fence, t)
            return {"loss": loss_vals, "fence": fence,
                    "acc": {bkt: grad_acc[bkt] for bkt in grad_cnt}}

        return traced

    # -- chunks --------------------------------------------------------------
    def _trace_chunk(self, r, node, prm, feeds, store, gathered,
                     grad_acc, grad_cnt, loss_vals):
        args = gather_chunk_args(self.dag, node, feeds, store)
        g = node.meta.get("param_from_comm")
        if node.bucket is not None:
            bparams = (gathered[g][node.bucket] if g in gathered
                       else prm.get(node.bucket))
        else:
            bparams = None

        # No lax.cond MEMBERSHIP gate: rank r's program contains only
        # rank r's tasks — that is the whole point of the MPMD
        # lowering.  The chunk body still runs inside a cond branch,
        # for numerics, not membership: a branch is its own XLA
        # computation, so the chunk compiles context-free — exactly
        # like the reference's per-chunk jit and the SPMD trace's
        # gated branch.  Inlined bare instead, XLA specializes the
        # body to its surroundings (seed-cotangent constants, fusion
        # into neighbors) and fp64 grads drift by ~1 ulp (observed on
        # dualpipev-z0).  The barrier keeps the always-true predicate
        # out of reach of conditional constant-folding.
        def run_fn(ops):
            bp, a = ops
            return node.fn(bp, *a)

        operands = (bparams, tuple(args))
        out_avals = jax.eval_shape(run_fn, operands)
        zeros = tree_map(lambda av: jnp.zeros(av.shape, av.dtype),
                         out_avals)
        pred = lax.optimization_barrier(jnp.asarray(True))
        outs = lax.cond(pred, run_fn, lambda _ops: zeros, operands)
        if node.meta.get("is_backward", False):
            bucket_grads = outs[0]
            cots = outs[1:]
            if node.bucket is not None and bucket_grads is not None:
                bkt = node.bucket
                grad_acc[bkt] = (bucket_grads if bkt not in grad_acc
                                 else tree_map(jnp.add, grad_acc[bkt],
                                               bucket_grads))
                grad_cnt[bkt] = grad_cnt.get(bkt, 0) + 1
            out_vals = cots
            out_slots = list(range(1, 1 + len(cots)))
        else:
            out_vals = outs
            out_slots = list(range(len(outs)))
        discard = set(node.meta.get("discard_out_slots", []))
        for slot, val in zip(out_slots, out_vals):
            if slot in discard or val is None:
                continue
            store[(node.id, slot)] = val
        for (nid, slot) in self.dag.outputs:
            if nid == node.id:
                loss_vals[(nid, slot)] = outs[slot]

    # -- p2p -----------------------------------------------------------------
    def _trace_send(self, r, node, store, toks):
        e_in = self.dag.in_edges(node.id)
        assert len(e_in) == 1, f"p2p with {len(e_in)} inputs"
        val = store[(e_in[0].src, e_in[0].src_out)]
        dsts = [d for (s, d) in node.meta["pairs"] if s == r]
        if not dsts:
            return
        nid = node.id

        def cb(v):
            payload = np.asarray(v)
            for d in dsts:
                self.transport.send(("p2p", self._gen, nid, r, d),
                                    payload)
            return np.zeros((), np.uint8)

        # ordered=True chains this into the rank's transport-op token
        # sequence, so sends post in program order
        tok = io_callback(cb, jax.ShapeDtypeStruct((), np.uint8), val,
                          ordered=True)
        toks.append(tok)

    def _trace_recv(self, r, node, store, built):
        src = None
        for (s, d) in node.meta["pairs"]:
            if d == r:
                src = s   # last match, mirroring Interpreter._exec_recv
        if src is None:
            return
        # wire shape from the oracle walk (edge ValueSpecs are logical,
        # pre-DP-shard shapes — the oracle saw what actually moves)
        wire = built.p2p_shapes.get((node.id, r))
        if wire is None:
            e_in = self.dag.in_edges(node.id)
            spec = e_in[0].spec
            wire = (tuple(spec.shape), np.dtype(spec.dtype))
        shape, dt = tuple(wire[0]), np.dtype(wire[1])
        nid = node.id

        def cb():
            v = self.transport.recv(("p2p", self._gen, nid, src, r),
                                    self.timeout)
            if tuple(v.shape) != shape or np.dtype(v.dtype) != dt:
                raise MpmdTransportError(
                    f"p2p payload on channel rank {src} -> rank {r} "
                    f"(node {nid}) arrived as {v.dtype}{list(v.shape)} "
                    f"but the receiver was wired for {dt}{list(shape)}")
            return v

        store[(node.id, 0)] = io_callback(
            cb, jax.ShapeDtypeStruct(shape, dt), ordered=True)

    # -- collectives ---------------------------------------------------------
    def _group_of(self, node) -> list[int]:
        return sorted(set(node.group or node.devices))

    def _trace_param_gather(self, r, node, prm, gathered):
        buckets = node.meta.get("buckets") or [node.meta["bucket"]]
        group = self._group_of(node)
        g = len(group)
        if g <= 1:
            gathered[node.id] = {b: prm[b] for b in buckets}
            return
        # fused buckets cross the wire as ONE concatenated payload per
        # word width
        cats, metas = _concat_words([prm[bkt] for bkt in buckets])
        pos = group.index(r)
        nid = node.id
        fulls = {}
        for dt, cat in cats.items():
            total = int(cat.size)
            chunk = -(-total // g)  # ceil: pad to g equal shards
            padded = (jnp.concatenate(
                [cat, jnp.zeros((chunk * g - total,), cat.dtype)])
                if chunk * g != total else cat)
            shard = padded[pos * chunk:(pos + 1) * chunk]

            def cb(sh, dt=dt, total=total):
                parts = self.transport.gather(
                    ("gather", self._gen, nid, dt), pos, g, np.asarray(sh),
                    self.timeout)
                return np.concatenate(parts)[:total]

            fulls[dt] = io_callback(
                cb, jax.ShapeDtypeStruct((total,), cat.dtype), shard,
                ordered=True)
        gathered[node.id] = dict(zip(buckets, _split_words(fulls, metas)))

    def _trace_grad_reduce(self, r, node, grad_acc, grad_cnt, built,
                           toks):
        group = self._group_of(node)
        g = len(group)
        pos = group.index(r)
        members = [(m["bucket"], bool(m.get("accumulated")))
                   for m in node.meta.get("fused_members") or [node.meta]
                   if not m.get("part", 0)]
        # which member buckets THIS rank contributes is trace-static
        contrib = {bkt: grad_cnt[bkt] for bkt, _acc in members
                   if bkt in grad_acc}
        payload_trees = {bkt: grad_acc[bkt] for bkt in contrib}
        nid = node.id
        owner = pos == 0  # the group's lowest rank folds and records

        def cb(trees):
            # jax may hand callback args over as jax.Arrays; the fold
            # below MUST stay pure numpy — a jnp op here dispatches a
            # fresh jit from inside an XLA host callback, which
            # deadlocks against the very programs this rendezvous is
            # waiting on (device busy -> dispatch queues -> rendezvous
            # never completes)
            np_trees = {bkt: (contrib[bkt], tree_map(np.asarray, t))
                        for bkt, t in trees.items()}
            posts = self.transport.gather(
                ("reduce", self._gen, nid), pos, g, (r, np_trees),
                self.timeout)
            if owner:
                by_dev = {d: data for (d, data) in posts}
                fold = built.reduce_fold.get(nid) or group
                for bkt, accumulated in members:
                    xs, cnts = [], []
                    for d in fold:
                        if bkt in by_dev.get(d, {}):
                            c, t = by_dev[d][bkt]
                            xs.append(t)
                            cnts.append(c)
                    if not xs:
                        continue  # no contributions yet (mirrors ref)
                    # the reference formula, in the reference member
                    # fold order (builtin sum from 0: same -0.0+0
                    # normalization as the interpreter's jnp version)
                    mean = tree_map(
                        lambda *ls: sum(x / c for x, c
                                        in zip(ls, cnts)) / len(ls),
                        *xs)
                    with self._events_lock:
                        self._events.append((bkt, accumulated, mean))
            return np.zeros((), np.uint8)

        tok = io_callback(cb, jax.ShapeDtypeStruct((), np.uint8),
                          payload_trees, ordered=True)
        toks.append(tok)
        for bkt in contrib:   # grads were consumed by the reduction
            grad_acc.pop(bkt, None)
            grad_cnt.pop(bkt, None)

    def _trace_a2a(self, r, node, store):
        e_in = self.dag.in_edges(node.id)
        assert len(e_in) == 1, f"a2a with {len(e_in)} inputs"
        val = store[(e_in[0].src, e_in[0].src_out)]
        group = self._group_of(node)
        g = len(group)
        if g <= 1:
            store[(node.id, 0)] = lax.optimization_barrier(val)
            return
        pos = group.index(r)
        nid = node.id

        def cb(v):
            # dispatch + return round trip: this rank's block crosses
            # the transport and comes back (identity values — the
            # reference runtime models EP math shard-locally)
            parts = self.transport.gather(
                ("a2a", self._gen, nid), pos, g, np.asarray(v),
                self.timeout)
            return parts[pos]

        store[(node.id, 0)] = io_callback(
            cb, jax.ShapeDtypeStruct(val.shape, val.dtype), val,
            ordered=True)

    def _trace_passthrough(self, node, store, *, barrier: bool):
        for e in self.dag.in_edges(node.id):
            val = store[(e.src, e.src_out)]
            store[(node.id, 0)] = (lax.optimization_barrier(val)
                                   if barrier else val)

    # ------------------------------------------------------------ dispatch
    def _dispatch(self, b: _Built, feeds_by_rank):
        """One multi-controller step: N threads each drive their rank's
        jit program on its own device; any rank failure poisons the
        transport so peers fail fast instead of hanging."""
        self._gen += 1
        self.transport.reset()
        with self._events_lock:
            self._events = []
        outs: dict[int, Any] = {}
        errors: dict[int, BaseException] = {}
        # compile barrier: a rank that compiles fast must not start
        # executing (and its transport timeouts ticking) while a peer
        # is still lowering — big models compile rank programs in
        # minutes, far beyond any sane recv timeout.  Each worker AOT-
        # compiles first, then all ranks cross the barrier together.
        gate = threading.Barrier(len(self.devices))

        def worker(r: int) -> None:
            try:
                dev = self._devmap[r]
                prm = jax.device_put(self.params, dev)
                fd = {k: jax.device_put(v, dev)
                      for k, v in feeds_by_rank[r].items()}
                try:
                    if r not in b.compiled:
                        b.compiled[r] = b.fns[r].lower(prm, fd).compile() \
                            if hasattr(b.fns[r], "lower") else b.fns[r]
                    gate.wait(timeout=max(self.timeout, 600.0))
                except BaseException:
                    gate.abort()  # free peers parked at the barrier
                    raise
                # device_get: rank outputs land on rank-local devices;
                # the controller epilogue folds across ranks, so bring
                # every leaf to host (numpy) before mixing them
                outs[r] = jax.device_get(
                    jax.block_until_ready(b.compiled[r](prm, fd)))
            except BaseException as e:
                errors[r] = e
                self.transport.abort(f"rank {r} failed: {e}")

        threads = [threading.Thread(target=worker, args=(r,),
                                    name=f"mpmd-rank{r}")
                   for r in self.devices]
        for t in threads:
            t.start()
        # first dispatch pays AOT compile before the barrier opens;
        # grant it the same generous budget the compile gate uses
        compile_grace = (0.0 if all(r in b.compiled for r in self.devices)
                         else max(self.timeout, 600.0))
        deadline = time.monotonic() + self.timeout + 30 + compile_grace
        for t in threads:
            t.join(max(0.1, deadline - time.monotonic()))
        if any(t.is_alive() for t in threads):
            self.transport.abort("controller join timeout")
            for t in threads:
                t.join(5)
            raise MpmdTransportError(
                "rank program(s) did not finish within the controller "
                "deadline — transport poisoned")
        if errors:
            r, e = sorted(errors.items())[0]
            raise e
        with self._events_lock:
            events = list(self._events)
        return outs, events

    # ------------------------------------------------------------ run
    def run(self, batch: dict[str, Any]) -> RunResult:
        b = self._ensure_built(batch)
        outs, events = self._dispatch(b, self._rank_feeds(batch))
        # loss: reference append order, same stack/mean ops
        losses = [outs[d]["loss"][(nid, slot)]
                  for (nid, slot, d) in b.replay.loss_order]
        loss = float(jnp.mean(jnp.stack(losses)))
        # reduced buckets: replay the interpreter's reduced/reduced_cnt
        # state machine over the owner-recorded reduction events (per
        # bucket the event order IS schedule order — each group's next
        # rendezvous cannot complete before every member passed the
        # previous one)
        reduced: dict[str, Any] = {}
        reduced_cnt: dict[str, int] = {}
        for (bkt, accumulated, mean) in events:
            if bkt in reduced and not accumulated:
                reduced[bkt] = tree_map(jnp.add, reduced[bkt], mean)
                reduced_cnt[bkt] += 1
            else:
                reduced[bkt] = mean
                reduced_cnt[bkt] = 1
        grads: dict[str, Any] = {}
        for bkt, tree in reduced.items():
            cnt = reduced_cnt[bkt]
            grads[bkt] = tree_map(lambda x: jnp.asarray(x / cnt), tree)
        # never-reduced buckets: reference device fold order
        per_bucket: dict[str, list] = {}
        for (bkt, d) in b.replay.grad_key_order:
            if bkt in grads or bkt not in outs[d]["acc"]:
                continue
            cnt = b.acc_cnt[(bkt, d)]
            per_bucket.setdefault(bkt, []).append(
                tree_map(lambda x: x / cnt, outs[d]["acc"][bkt]))
        for bkt, gs in per_bucket.items():
            acc = gs[0]
            for g2 in gs[1:]:
                acc = tree_map(jnp.add, acc, g2)
            grads[bkt] = tree_map(lambda x: x / len(gs), acc)
        return RunResult(
            loss=loss, grads=grads, ledgers={},
            exec_order=list(b.replay.exec_order),
            stats={"backend": "mpmd", "tasks": b.n_tasks,
                   "losses": len(losses), "devices": self.n,
                   "transport": self.transport.name,
                   "reduce_events": len(events)})

    # ------------------------------------------------------------ protocol
    @classmethod
    def compile(cls, prog: CompiledProgram,
                params: Optional[dict[str, Any]] = None, *,
                physical_devices: Optional[Sequence[int]] = None,
                **opts) -> "MpmdExecutor":
        return cls(prog, params, physical_devices=physical_devices,
                   **opts)

    def measure(self, batch: dict[str, Any], reps: int = 3,
                warmup: int = 1) -> float:
        """Wall-clock seconds per multi-controller step (min over
        ``reps`` after ``warmup`` dispatches) — includes per-rank
        dispatch, transport waits, and host device_put, i.e. the real
        MPMD step critical path."""
        if reps < 1:
            raise ValueError(f"measure needs reps >= 1, got {reps}")
        b = self._ensure_built(batch)
        feeds = self._rank_feeds(batch)
        for _ in range(max(warmup, 0)):
            self._dispatch(b, feeds)
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            self._dispatch(b, feeds)
            times.append(time.perf_counter() - t0)
        return min(times)

    def trace_sizes(self, batch: dict[str, Any]) -> dict[int, int]:
        """Per-rank traced program size (total jaxpr equation count,
        sub-jaxprs included) — the acceptance metric: every rank's
        count must be strictly below the SPMD whole-mesh trace
        (``SpmdExecutor.trace_size``) for world >= 4."""
        b = self._ensure_built(batch)
        feeds = self._rank_feeds(batch)
        return {r: jaxpr_eqn_count(
            jax.make_jaxpr(b.traced[r])(self.params, feeds[r]))
            for r in self.devices}

    def close(self) -> None:
        self.transport.close()
