"""SPMD plan executor: run a compiled ``GlobalPlan`` on real XLA devices.

The reference ``Interpreter`` *simulates* devices (one Python loop, one
jit per chunk, no wire traffic).  This module lowers the same plan into
ONE ``jax.jit`` + ``shard_map`` program over N real XLA devices — on the
CPU, host-platform devices faked with
``--xla_force_host_platform_device_count``
(``launch.hostdevices.ensure_host_devices``); on TPU, the physical
chips — so every collective in the plan becomes a real XLA collective on
the wire, in the plan's dispatch order.

IR-op -> lax lowering (DESIGN.md §12 has the full table):

  chunk                 traced compute, ``lax.cond``-gated on membership
                        of the chunk's device set (non-members take a
                        zeros branch, so at runtime each rank executes
                        only its own plan slice)
  p2p send/recv         ``lax.ppermute`` with the node's (src, dst)
                        pairs (non-destinations receive zeros)
  all_gather (param)    the bucket's params, bit-cast to one unsigned
                        word vector per width, sharded 1/|group| per
                        rank and reassembled with
                        ``lax.all_gather(tiled=True)`` over the
                        subgroup; consuming chunks read the GATHERED
                        tree (the collective is load-bearing — XLA
                        cannot dead-code it away).  A fused node
                        (overlap engine) concatenates its member
                        buckets' words into ONE collective per width.
  all_reduce (grad)     ``lax.psum`` of the locally accumulated,
                        1/count-prescaled bucket grads over the replica
                        subgroup (fused members concatenate per dtype
                        into one collective)
  reduce_scatter (grad) ``lax.psum_scatter(tiled=True)`` over the
                        subgroup; an epilogue ``all_gather`` immediately
                        reassembles the full mean so the executor can
                        return the reference RunResult contract (full
                        grads).  Real ZeRO keeps the shard — the extra
                        gather is parity bookkeeping, and is part of
                        what this harness measures.
  all_to_all (EP)       an involutive double ``lax.all_to_all`` round
                        trip over the expert subgroup: real dispatch +
                        return bytes on the wire, bit-identical values
                        (the reference runtime models EP math as
                        shard-local with the full expert stack)
  d2h / h2d (Offload)   documented on-device fallback:
                        ``lax.optimization_barrier`` identity.  Host
                        callbacks would serialize the whole program on
                        CPU hosts; the barrier keeps the node's ordering
                        without modelling DMA time.

Bit-parity with the reference interpreter is by construction: the
executor traces nodes in the interpreter's OWN dynamic dispatch order
(``interpreter.replay_schedule`` — a schedule-only replay of the worker
loop, including the FSDP-style gather rate limiter), accumulates
gradients and losses in that order, and applies exactly the reference
reduction formulas (``sum(x/c)/n`` then the per-microbatch fold).  With
replica groups of size 2 every cross-rank sum is order-free in IEEE
arithmetic, so fp64 loss/grads match the interpreter bit for bit
(tests/test_spmd_executor.py).

What the host-device harness measures — and does not:

  * measures: the XLA-compiled critical path of the fused program —
    real collective dispatch, real inter-device copies on the host
    platform, cond-gated per-rank compute;
  * does not: HBM pressure (host RAM is shared), ICI/DCN link time
    (host "links" are memcpy), host-offload DMA (barrier fallback), or
    overlap of compute with communication (XLA's CPU collectives are
    synchronous).  Measured/predicted ratios (benchmarks/
    bench_spmd_parity.py) are therefore calibration inputs
    (``tune.measured``), not absolute claims.

A plan that fails ``validate_comm_order`` is rejected at construction,
BEFORE tracing — the static analogue of the hang such a plan would
cause on a real multi-controller cluster.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh as XlaMesh
from jax.sharding import PartitionSpec as P

from ..core.compiler import CompiledProgram
from ..core.dag import Node, TrainingDAG
from ..core.plan import ROLE_SEND
from ..core.scheduler import validate_comm_order
from .executor import jaxpr_eqn_count, register_backend
from .interpreter import RunResult, ScheduleReplay, _PlanWalker

AXIS = "spmd"

tree_map = jax.tree_util.tree_map
tree_flatten = jax.tree_util.tree_flatten
tree_unflatten = jax.tree_util.tree_unflatten
tree_leaves = jax.tree_util.tree_leaves


# ---------------------------------------------------------------------------
# flat codecs (bit-exact tree <-> per-dtype vectors, for wire collectives)
# ---------------------------------------------------------------------------

def _flatten_by_dtype(tree):
    """Flatten a (gradient) pytree into one 1-D vector per dtype.
    Returns ({dtype_str: flat}, recipe)."""
    leaves, treedef = tree_flatten(tree)
    parts: dict[str, list] = {}
    recipe = []
    for l in leaves:
        dt = str(l.dtype)
        lst = parts.setdefault(dt, [])
        off = sum(int(x.size) for x in lst)
        lst.append(l.reshape(-1))
        recipe.append((dt, off, int(l.size), tuple(l.shape)))
    flats = {dt: (jnp.concatenate(lst) if len(lst) > 1 else lst[0])
             for dt, lst in parts.items()}
    return flats, (treedef, recipe)


def _unflatten_by_dtype(flats, recipe):
    treedef, leaf_recipe = recipe
    leaves = [flats[dt][off:off + n].reshape(shape)
              for (dt, off, n, shape) in leaf_recipe]
    return tree_unflatten(treedef, leaves)


_WORDS = {1: jnp.uint8, 2: jnp.uint16, 4: jnp.uint32, 8: jnp.uint64}


def _tree_to_words(tree):
    """Bit-exact flattening for gathers: each leaf is bitcast to the
    unsigned int of its own width and flattened per width.  (Bitcasting
    to bytes instead adds a trailing dim of itemsize, which the TPU tiles
    to 128 lanes: a 64-fold blow-up of a bf16 bucket.)  Returns
    ({word dtype: 1-D}, recipe); ``_words_to_tree`` inverts."""
    dtypes = tree_map(lambda l: jnp.dtype(l.dtype), tree)
    words = tree_map(lambda l: lax.bitcast_convert_type(
        l, _WORDS[jnp.dtype(l.dtype).itemsize]), tree)
    flats, recipe = _flatten_by_dtype(words)
    return flats, (recipe, dtypes)


def _words_to_tree(flats, recipe):
    recipe, dtypes = recipe
    return tree_map(lax.bitcast_convert_type,
                    _unflatten_by_dtype(flats, recipe), dtypes)


def _concat_flats(flats_list):
    """Concatenate several trees' per-dtype flats (a fused collective's
    members) into one vector per dtype.  Returns ({dtype: 1-D},
    [{dtype: (offset, size)}] per member)."""
    per_dt: dict[str, list] = {}
    bounds = []
    for flats in flats_list:
        b = {}
        for dt, flat in flats.items():
            lst = per_dt.setdefault(dt, [])
            b[dt] = (sum(int(x.size) for x in lst), int(flat.size))
            lst.append(flat)
        bounds.append(b)
    cats = {dt: (jnp.concatenate(lst) if len(lst) > 1 else lst[0])
            for dt, lst in per_dt.items()}
    return cats, bounds


def _split_flats(fulls, bounds):
    """Inverse of ``_concat_flats``: each member's per-dtype flats."""
    return [{dt: fulls[dt][off:off + n] for dt, (off, n) in b.items()}
            for b in bounds]


def _concat_words(trees):
    """``_concat_flats`` over the word flattening of each tree.  Returns
    ({word dtype: 1-D}, metas); ``_split_words`` inverts."""
    words = [_tree_to_words(t) for t in trees]
    cats, bounds = _concat_flats([f for f, _ in words])
    return cats, list(zip([r for _, r in words], bounds))


def _split_words(fulls, metas):
    recipes = [r for r, _ in metas]
    return [_words_to_tree(f, r) for f, r in
            zip(_split_flats(fulls, [b for _, b in metas]), recipes)]


def gather_chunk_args(dag: TrainingDAG, node: Node, feeds, store):
    """``Interpreter._gather_chunk_inputs`` on rank-local (nid, slot)
    keys: multi-source cotangent slots sum in edge order; seed/zero
    cotangent slots materialize from the forward's out_specs.  Shared
    by the SPMD trace (one whole-mesh program) and the MPMD per-rank
    traces (``runtime/mpmd.py``) — one source of truth for how a traced
    chunk assembles its inputs."""
    m = node.meta.get("n_inputs", 0)
    args: list = []
    for slot in range(m):
        key = (node.id, slot)
        if key in feeds:
            args.append(feeds[key])
            continue
        vals = [store[(e.src, e.src_out)]
                for e in dag.in_edges(node.id)
                if e.dst_in == slot]
        if not vals:
            if slot in node.meta.get("zero_cot_slots", []) \
                    or slot in node.meta.get("seed_slots", []):
                args.append(None)
                continue
            raise KeyError(
                f"no value for {node.short()} slot {slot}")
        args.append(vals[0] if len(vals) == 1
                    else sum(vals[1:], vals[0]))
    if "fwd_node" in node.meta:
        fwd = dag.nodes[node.meta["fwd_node"]]
        n_cots = node.meta.get("n_cots", fwd.n_outputs)
        m0 = node.meta["n_inputs"] - n_cots
        for slot in node.meta.get("seed_slots", []):
            s = fwd.out_specs[slot - m0]
            args[slot] = jnp.ones(s.shape, dtype=s.dtype)
        for slot in node.meta.get("zero_cot_slots", []):
            s = fwd.out_specs[slot - m0]
            args[slot] = jnp.zeros(s.shape, dtype=s.dtype)
    return args


@dataclass
class _Built:
    """One traced+jitted program (per batch-shape signature) plus the
    trace-time bookkeeping the extraction epilogue reads."""
    fn: Any
    replay: ScheduleReplay
    reduced_cnt: dict = field(default_factory=dict)    # bucket -> int
    red_group: dict = field(default_factory=dict)      # bucket -> devices
    acc_cnt: dict = field(default_factory=dict)        # bucket -> int
    n_tasks: int = 0
    traced_sm: Any = None   # unjitted shard_map fn (trace_size probes it)


class SpmdBackendError(RuntimeError):
    """The SPMD executor cannot run this plan on the available devices
    (too few XLA devices, or a collective group the 1-D axis cannot
    express)."""


@register_backend("spmd")
class SpmdExecutor:
    """Execute a ``CompiledProgram`` as one jit+shard_map SPMD program
    over ``len(plan.devices)`` real XLA devices.

    ``gate_compute=False`` disables the per-chunk ``lax.cond`` rank
    gates (every rank computes every chunk) — numerics are unchanged;
    only useful for debugging XLA cond issues."""

    def __init__(self, prog: CompiledProgram,
                 params: Optional[dict[str, Any]] = None, *,
                 gate_compute: bool = True,
                 gather_limit: Optional[int] = None,
                 physical_devices: Optional[Sequence[int]] = None) -> None:
        # hang detection: reject invalid comm orders BEFORE tracing —
        # the dynamic analogue is a rendezvous deadlock on real ranks
        validate_comm_order(prog.dag, prog.plan)
        self.prog = prog
        self.dag = prog.dag
        self.plan = prog.plan
        self.params = params if params is not None else prog.params
        self.gate_compute = gate_compute
        self.gather_limit = gather_limit
        self.devices = sorted(self.plan.devices)
        self.n = len(self.devices)
        self._idx = {d: i for i, d in enumerate(self.devices)}
        avail = jax.devices()
        if len(avail) < self.n:
            platform = avail[0].platform
            if platform != "cpu":
                raise SpmdBackendError(
                    f"plan spans {self.n} devices but platform "
                    f"{platform} has {len(avail)} "
                    f"({avail[0].device_kind})")
            raise SpmdBackendError(
                f"plan spans {self.n} devices but jax sees only "
                f"{len(avail)} cpu device(s); fake host devices with "
                "launch.hostdevices.ensure_host_devices(n) BEFORE jax "
                "initializes (tests use a subprocess with XLA_FLAGS="
                f"--xla_force_host_platform_device_count={self.n})")
        if physical_devices is not None:
            # elastic recovery: map the n logical plan ranks onto the
            # SURVIVING physical devices (by jax.devices() index), so a
            # shrunk-world program never touches the failed chip.  The
            # same mapping RE-expands on regrowth: survivors keep their
            # slots and replacement devices fill the new trailing ranks
            # (any distinct index set works — the indices need not be
            # contiguous or sorted, so a world regrown around a dead
            # chip simply never names it)
            phys = [int(p) for p in physical_devices]
            if len(phys) != self.n:
                raise SpmdBackendError(
                    f"plan spans {self.n} devices but physical_devices "
                    f"names {len(phys)}: {phys}")
            bad = [p for p in phys if not 0 <= p < len(avail)]
            if bad or len(set(phys)) != len(phys):
                raise SpmdBackendError(
                    f"physical_devices must be {len(phys)} distinct "
                    f"indices into jax.devices() (0..{len(avail)-1}), "
                    f"got {phys}")
            chosen = [avail[p] for p in phys]
        else:
            chosen = avail[:self.n]
        self.physical_devices = tuple(
            d.id if hasattr(d, "id") else i for i, d in enumerate(chosen))
        self.mesh = XlaMesh(np.array(chosen), (AXIS,))
        self._built: dict[tuple, _Built] = {}
        # feed resolution reuses the interpreter's input distribution
        # rules verbatim (one source of truth for microbatch slicing)
        self._resolver = _PlanWalker(prog, gather_limit=gather_limit)

    # ------------------------------------------------------------ helpers
    def _sig(self, batch) -> tuple:
        # cache key from shape/dtype attributes only — np.asarray here
        # would force a device-to-host transfer per call on real chips
        return tuple(sorted(
            (k, tuple(np.shape(v)),
             str(getattr(v, "dtype", None) or np.asarray(v).dtype))
            for k, v in batch.items()))

    def _axis_groups(self, group_devices):
        """(group_size, axis_index_groups) for a collective over plan
        devices.  The 1-D SPMD axis can express a subgroup only as a
        partition into equal contiguous aligned runs — which rank-major
        ``core.strategy.Mesh`` device groups always are."""
        gidx = sorted(self._idx[d] for d in group_devices)
        g = len(gidx)
        if g == self.n and gidx == list(range(self.n)):
            return g, None
        lo = gidx[0]
        if gidx == list(range(lo, lo + g)) and lo % g == 0 \
                and self.n % g == 0:
            return g, [list(range(i * g, (i + 1) * g))
                       for i in range(self.n // g)]
        raise SpmdBackendError(
            f"collective group {tuple(group_devices)} is not a contiguous "
            f"aligned run of the {self.n}-rank SPMD axis; rank-major mesh "
            "device groups always are (custom RawDirectives placements "
            "may not be)")

    def _member_pred(self, rank, devs):
        gidx = [self._idx[d] for d in devs]
        if len(gidx) == 1:
            return rank == gidx[0]
        return jnp.isin(rank, jnp.asarray(gidx))

    def _stack_feeds(self, batch):
        """Per-(consumer, slot) rank-major stacked feed arrays: slice r
        holds what plan device r consumes (zeros on non-consumers);
        shard_map's ``P(AXIS)`` in_spec hands each rank its slice."""
        feeds3 = self._resolver._resolve_inputs(batch)
        by_key: dict[tuple, dict[int, np.ndarray]] = {}
        for (nid, slot, d), v in feeds3.items():
            by_key.setdefault((nid, slot), {})[d] = np.asarray(v)
        stacked = {}
        for k, per_dev in sorted(by_key.items()):
            sample = next(iter(per_dev.values()))
            arr = np.zeros((self.n,) + sample.shape, sample.dtype)
            for d, v in per_dev.items():
                arr[self._idx[d]] = v
            stacked[k] = jnp.asarray(arr)
        return stacked

    # ------------------------------------------------------------ build
    def _build(self, batch) -> _Built:
        replay = self._resolver.replay(batch)
        b = _Built(fn=None, replay=replay,
                   n_tasks=sum(p.n_tasks()
                               for p in self.plan.device_plans.values()))
        # first-occurrence node trace order from the replayed dispatch
        trace_order: list[int] = []
        seen: set[int] = set()
        for (nid, _dev, role) in replay.exec_order:
            if role == ROLE_SEND or nid in seen:
                continue
            seen.add(nid)
            trace_order.append(nid)
        traced = self._make_traced(trace_order, b)
        sm = jax.shard_map(traced, mesh=self.mesh,
                           in_specs=(P(), P(AXIS)), out_specs=P(AXIS),
                           check_vma=False)
        b.traced_sm = sm
        b.fn = jax.jit(sm)
        return b

    # ------------------------------------------------------------ tracing
    def _make_traced(self, trace_order, built: _Built):
        dag, params = self.dag, self.params

        def traced(prm, feeds_in):
            rank = lax.axis_index(AXIS)
            feeds = {k: v[0] for k, v in feeds_in.items()}  # local block
            store: dict[tuple[int, int], Any] = {}
            gathered: dict[int, dict[str, Any]] = {}
            grad_acc: dict[str, Any] = {}
            grad_cnt: dict[str, int] = {}
            acc_devs: dict[str, set] = {}
            reduced: dict[str, Any] = {}
            loss_vals: dict[tuple[int, int], Any] = {}

            for nid in trace_order:
                node = dag.nodes[nid]
                if node.is_chunk:
                    self._trace_chunk(node, rank, prm, feeds, store,
                                      gathered, grad_acc, grad_cnt,
                                      acc_devs, loss_vals, built)
                elif node.op == "p2p":
                    self._trace_p2p(node, store)
                elif node.op == "all_gather" and node.payload == "param":
                    self._trace_param_gather(node, rank, prm, gathered)
                elif node.op in ("all_reduce", "reduce_scatter") \
                        and node.payload == "grad":
                    self._trace_grad_reduce(node, grad_acc, grad_cnt,
                                            acc_devs, reduced, built)
                elif node.op in ("d2h", "h2d"):
                    self._trace_passthrough(node, store, barrier=True)
                elif node.op == "all_to_all":
                    self._trace_a2a(node, store)
                else:  # broadcast / generic activation collective
                    self._trace_passthrough(node, store, barrier=False)

            for bkt, cnt in grad_cnt.items():   # never-reduced buckets
                built.acc_cnt[bkt] = cnt
            out = {
                "loss": {k: v[None] for k, v in loss_vals.items()},
                "reduced": tree_map(lambda x: x[None], reduced),
                "acc": {bkt: tree_map(lambda x: x[None], grad_acc[bkt])
                        for bkt in grad_cnt},
            }
            return out

        return traced

    # -- chunks --------------------------------------------------------------
    def _trace_chunk(self, node, rank, prm, feeds, store, gathered,
                     grad_acc, grad_cnt, acc_devs, loss_vals, built):
        args = gather_chunk_args(self.dag, node, feeds, store)
        g = node.meta.get("param_from_comm")
        if node.bucket is not None:
            bparams = (gathered[g][node.bucket] if g in gathered
                       else prm.get(node.bucket))
        else:
            bparams = None

        def run_fn(ops):
            bp, a = ops
            return node.fn(bp, *a)

        operands = (bparams, tuple(args))
        devs = node.devices or self.devices
        gate = self.gate_compute and set(devs) != set(self.devices)
        if gate:
            out_avals = jax.eval_shape(run_fn, operands)
            zeros = tree_map(lambda a: jnp.zeros(a.shape, a.dtype),
                             out_avals)
            pred = self._member_pred(rank, devs)
            outs = lax.cond(pred, run_fn, lambda _ops: zeros, operands)
        else:
            outs = run_fn(operands)

        if node.meta.get("is_backward", False):
            bucket_grads = outs[0]
            cots = outs[1:]
            if node.bucket is not None and bucket_grads is not None:
                bkt = node.bucket
                grad_acc[bkt] = (bucket_grads if bkt not in grad_acc
                                 else tree_map(jnp.add, grad_acc[bkt],
                                               bucket_grads))
                grad_cnt[bkt] = grad_cnt.get(bkt, 0) + 1
                acc_devs.setdefault(bkt, set()).update(devs)
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

    # -- comms ---------------------------------------------------------------
    def _trace_p2p(self, node, store):
        e_in = self.dag.in_edges(node.id)
        assert len(e_in) == 1, f"p2p with {len(e_in)} inputs"
        e = e_in[0]
        val = store[(e.src, e.src_out)]
        perm = [(self._idx[s], self._idx[d])
                for (s, d) in node.meta["pairs"]]
        store[(node.id, 0)] = lax.ppermute(val, AXIS, perm)

    def _trace_passthrough(self, node, store, *, barrier: bool):
        for e in self.dag.in_edges(node.id):
            val = store[(e.src, e.src_out)]
            store[(node.id, 0)] = (lax.optimization_barrier(val)
                                   if barrier else val)

    def _trace_a2a(self, node, store):
        e_in = self.dag.in_edges(node.id)
        assert len(e_in) == 1, f"a2a with {len(e_in)} inputs"
        e = e_in[0]
        val = store[(e.src, e.src_out)]
        g, subs = self._axis_groups(node.group or node.devices)
        if g > 1 and val.ndim >= 1 and val.shape[0] % g == 0:
            # involutive round trip: dispatch + return on the wire,
            # identity on the values (matches the reference runtime's
            # shard-local EP numerics)
            fwd = lax.all_to_all(val, AXIS, split_axis=0, concat_axis=0,
                                 axis_index_groups=subs, tiled=True)
            val = lax.all_to_all(fwd, AXIS, split_axis=0, concat_axis=0,
                                 axis_index_groups=subs, tiled=True)
        else:
            val = lax.optimization_barrier(val)
        store[(node.id, 0)] = val

    def _trace_param_gather(self, node, rank, prm, gathered):
        buckets = node.meta.get("buckets") or [node.meta["bucket"]]
        g, subs = self._axis_groups(node.group or node.devices)
        if g <= 1:
            gathered[node.id] = {b: prm[b] for b in buckets}
            return
        # fused buckets lower as ONE concatenated collective per word
        # width (one in all for a bf16 model)
        cats, metas = _concat_words([prm[b] for b in buckets])
        pos = rank % g  # local position within the aligned subgroup
        fulls = {}
        for dt, cat in cats.items():
            total = int(cat.size)
            chunk = -(-total // g)  # ceil: pad to g equal shards
            padded = (jnp.concatenate(
                [cat, jnp.zeros((chunk * g - total,), cat.dtype)])
                if chunk * g != total else cat)
            shard = lax.dynamic_slice(padded, (pos * chunk,), (chunk,))
            fulls[dt] = lax.all_gather(shard, AXIS, axis_index_groups=subs,
                                       tiled=True)[:total]
        gathered[node.id] = dict(zip(buckets, _split_words(fulls, metas)))

    def _trace_grad_reduce(self, node, grad_acc, grad_cnt, acc_devs,
                           reduced, built):
        g, subs = self._axis_groups(node.group or node.devices)
        group = set(node.group or node.devices)
        members = []
        for m in node.meta.get("fused_members") or [node.meta]:
            if m.get("part", 0) != 0:
                continue  # bucket_sz parts: numerics once, on part 0
            bkt = m["bucket"]
            if bkt not in grad_acc:
                continue  # no contributions yet (mirrors interpreter)
            members.append((bkt, bool(m.get("accumulated"))))
        if not members:
            return
        # pre-scale each contribution by 1/count (reference formula
        # sum(x/c)/n), flatten, and run ONE collective per dtype over
        # the concatenated fused payload
        scaled, recipes, contrib = [], [], []
        for bkt, _acc in members:
            cnt = grad_cnt[bkt]
            tr = tree_map(lambda x: x / cnt, grad_acc[bkt])
            flats, recipe = _flatten_by_dtype(tr)
            scaled.append(flats)
            recipes.append(recipe)
            contrib.append(max(len(acc_devs.get(bkt, set()) & group), 1))
        cats, bounds = _concat_flats(scaled)
        summed: dict[str, Any] = {}
        for dt, cat in cats.items():
            if g <= 1:
                summed[dt] = cat
            elif node.op == "all_reduce":
                summed[dt] = lax.psum(cat, AXIS, axis_index_groups=subs)
            else:  # reduce_scatter: real scatter + parity epilogue gather
                total = int(cat.size)
                chunk = -(-total // g)
                padded = (jnp.concatenate(
                    [cat, jnp.zeros((chunk * g - total,), cat.dtype)])
                    if chunk * g != total else cat)
                shard = lax.psum_scatter(padded, AXIS,
                                         axis_index_groups=subs,
                                         tiled=True)
                summed[dt] = lax.all_gather(
                    shard, AXIS, axis_index_groups=subs,
                    tiled=True)[:total]
        for (bkt, accumulated), recipe, flats, n_contrib in zip(
                members, recipes, _split_flats(summed, bounds), contrib):
            mean = tree_map(lambda x: x / n_contrib,
                            _unflatten_by_dtype(flats, recipe))
            if bkt in reduced and not accumulated:
                reduced[bkt] = tree_map(jnp.add, reduced[bkt], mean)
                built.reduced_cnt[bkt] += 1
            else:
                reduced[bkt] = mean
                built.reduced_cnt[bkt] = 1
            built.red_group[bkt] = tuple(sorted(group))
            grad_acc.pop(bkt, None)
            grad_cnt.pop(bkt, None)
            acc_devs.pop(bkt, None)

    # ------------------------------------------------------------ run
    def _ensure_built(self, batch) -> _Built:
        key = self._sig(batch)
        if key not in self._built:
            self._built[key] = self._build(batch)
        return self._built[key]

    def run(self, batch: dict[str, Any]) -> RunResult:
        b = self._ensure_built(batch)
        # feeds are re-stacked per call, never cached by signature: a
        # training loop passes same-shaped batches with NEW data every
        # step, so a signature-keyed cache would serve stale values.
        # The stacking is O(batch bytes) of host work — noise next to
        # the device step it feeds.
        feeds = self._stack_feeds(batch)
        out = b.fn(self.params, feeds)
        # loss: mean over per-task loss values in the reference append
        # order (same stack, same op, same element order)
        losses = [out["loss"][(nid, slot)][self._idx[d]]
                  for (nid, slot, d) in b.replay.loss_order]
        loss = float(jnp.mean(jnp.stack(losses)))
        grads: dict[str, Any] = {}
        for bkt, tree in out["reduced"].items():
            own = self._idx[b.red_group[bkt][0]]
            cnt = b.reduced_cnt[bkt]
            grads[bkt] = tree_map(lambda x: x[own] / cnt, tree)
        per_bucket_dev: dict[str, list] = {}
        for (bkt, d) in b.replay.grad_key_order:
            if bkt in grads or bkt not in out["acc"]:
                continue
            i = self._idx[d]
            cnt = b.acc_cnt[bkt]
            per_bucket_dev.setdefault(bkt, []).append(
                tree_map(lambda x: x[i] / cnt, out["acc"][bkt]))
        for bkt, gs in per_bucket_dev.items():
            acc = gs[0]
            for gg in gs[1:]:
                acc = tree_map(jnp.add, acc, gg)
            grads[bkt] = tree_map(lambda x: x / len(gs), acc)
        return RunResult(loss=loss, grads=grads, ledgers={},
                         exec_order=list(b.replay.exec_order),
                         stats={"backend": "spmd", "tasks": b.n_tasks,
                                "losses": len(losses),
                                "devices": self.n})

    def measure(self, batch: dict[str, Any], reps: int = 3,
                warmup: int = 1) -> float:
        """Wall-clock seconds per step of the compiled SPMD program
        (min over ``reps``, after ``warmup`` compile+run calls;
        ``warmup=0`` includes first-dispatch cost)."""
        if reps < 1:
            raise ValueError(f"measure needs reps >= 1, got {reps}")
        b = self._ensure_built(batch)
        feeds = self._stack_feeds(batch)
        for _ in range(max(warmup, 0)):
            jax.block_until_ready(b.fn(self.params, feeds))
        times = []
        for _ in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(b.fn(self.params, feeds))
            times.append(time.perf_counter() - t0)
        return min(times)

    # ------------------------------------------------------------ protocol
    @classmethod
    def compile(cls, prog: CompiledProgram,
                params: Optional[dict[str, Any]] = None, *,
                physical_devices: Optional[Sequence[int]] = None,
                **opts) -> "SpmdExecutor":
        return cls(prog, params, physical_devices=physical_devices,
                   **opts)

    def trace_size(self, batch: dict[str, Any]) -> int:
        """Whole-mesh traced program size (total jaxpr equation count,
        sub-jaxprs included) — every device carries this entire trace.
        The MPMD per-rank programs (``MpmdExecutor.trace_sizes``) must
        each come in strictly below it for world >= 4."""
        b = self._ensure_built(batch)
        feeds = self._stack_feeds(batch)
        return jaxpr_eqn_count(
            jax.make_jaxpr(b.traced_sm)(self.params, feeds))
