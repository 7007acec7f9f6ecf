"""The training step's layer names, as a profiler sees them.

Device scopes: ``jax.named_scope`` puts ``attn``, ``mlp`` and
``lm_head_ce`` on the model's ops and ``adamw`` on the optimizer's, in
the ``op_name`` metadata of the compiled step, which a device trace
reports as each op's ``tf_op``.  Every matmul of the step (forward,
remat recompute, backward, the flash attention's custom backward) must
lie under exactly one layer scope.

Host spans: ``Supervisor.run`` makes each step a profiler step
``train_step`` holding ``ft.sync`` and ``ft.metrics``; ``TokenLoader``
wraps its source's work in ``data.block``.
"""
import dataclasses
import functools
import hashlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.profiler import ProfileData

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.data import SyntheticTokenSource, TokenLoader
from repro.ft import Supervisor
from repro.launch.train import build_step
from repro.models import init
from repro.optim import adamw_init, cosine_schedule

LAYER_SCOPES = ("attn", "mlp", "lm_head_ce")
# a name-stack component, with the transforms that wrap it stripped:
# ``transpose(jvp(lm_head_ce))`` is ``lm_head_ce``
_WRAPPED = re.compile(r"[A-Za-z_][\w.]*\((.*)\)")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*.*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_FRAME = re.compile(r"stack_frame_id=(\d+)")


def components(op_name: str) -> list[str]:
    out = []
    for c in op_name.split("/"):
        while (m := _WRAPPED.fullmatch(c)):
            c = m.group(1)
        out.append(c)
    return out


def innermost_files(hlo: str) -> dict[int, str]:
    """Stack frame id -> the file of that (innermost) frame, from the
    tables that close an HLO module's text."""
    tables: dict[str, dict[int, str]] = {}
    current = None
    for line in hlo.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            current = tables.setdefault(line, {})
        elif current is not None and line[:1].isdigit():
            k, _, v = line.partition(" ")
            current[int(k)] = v
        else:
            current = None
    names = {k: v.strip('"') for k, v in tables["FileNames"].items()}
    loc_file = {k: int(re.search(r"file_name_id=(\d+)", v).group(1))
                for k, v in tables["FileLocations"].items()}
    return {k: names[loc_file[int(re.search(r"file_location_id=(\d+)",
                                            v).group(1))]]
            for k, v in tables["StackFrames"].items()}


def step_config(family: str):
    """A CPU-sized config of the family, bf16 under full remat as the
    benchmark's cells run it."""
    cfg = get_config(family).reduced(n_layers=2, d_model=64, d_ff=128,
                                     vocab=256, n_heads=4,
                                     dtype="bfloat16")
    return dataclasses.replace(cfg, remat="full")


@functools.lru_cache(maxsize=None)
def compiled_step_hlo(family: str) -> str:
    cfg = step_config(family)
    params = jax.eval_shape(lambda: init(cfg, jax.random.PRNGKey(0)))
    state = {"params": params, "opt": jax.eval_shape(adamw_init, params),
             "step": jax.ShapeDtypeStruct((), jnp.int32)}
    batch = {k: jax.ShapeDtypeStruct((2, 32), jnp.int32)
             for k in ("tokens", "labels")}
    step = build_step(cfg, cosine_schedule(1e-3, 100))
    return step.lower(state, batch).compile().as_text()


FAMILIES = ("qwen1.5-0.5b", "minicpm-2b")
# latent attention, a leading dense layer and an expert layer
MOE = "moonlight-16b-a3b"


def test_families_cover_bias_and_tied_head():
    qwen, minicpm = (get_config(f) for f in FAMILIES)
    assert qwen.qkv_bias and qwen.tie_embeddings
    assert not minicpm.qkv_bias


@pytest.mark.parametrize("family", FAMILIES + (MOE,))
def test_every_matmul_lies_under_one_layer_scope(family):
    hlo = compiled_step_hlo(family)
    seen = {s: 0 for s in LAYER_SCOPES}
    remat = backward = custom_bwd = 0
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(1) not in ("dot", "convolution"):
            continue
        name = _OP_NAME.search(line).group(1)
        comps = components(name)
        hit = [s for s in LAYER_SCOPES if s in comps]
        assert len(hit) == 1, name
        seen[hit[0]] += 1
        in_remat = "rematted_computation" in comps
        in_bwd = name.startswith("jit(step)/transpose(")
        remat += in_remat
        backward += in_bwd
        # the flash attention's custom backward: its loop over key
        # blocks inside ``attn``, under the transpose but not recomputed
        custom_bwd += (in_bwd and not in_remat and hit == ["attn"]
                       and "while" in comps[comps.index("attn"):])
    assert all(seen.values()), seen
    assert remat and backward and custom_bwd


def test_expert_layer_nests_moe_and_its_routing_in_mlp():
    # an expert layer's block lies under ``mlp`` as a dense MLP does, in
    # ``moe``; its router, top-k, sorts and the gather and scatter of
    # rows in ``moe_route`` inside that
    hlo = compiled_step_hlo(MOE)
    opcodes = {"moe_route": set(), "moe": set()}
    for line in hlo.splitlines():
        m, on = _INSTR.match(line), _OP_NAME.search(line)
        if not (m and on):
            continue
        comps = components(on.group(1))
        for scope in ("moe_route", "moe"):
            if scope in comps:
                assert "mlp" in comps[:comps.index(scope)], on.group(1)
                opcodes[scope].add(m.group(1))
        if m.group(1) == "sort":
            assert "moe_route" in comps, on.group(1)
    assert "sort" in opcodes["moe_route"] and "dot" in opcodes["moe"]


@pytest.mark.parametrize("family", FAMILIES)
def test_every_optimizer_op_lies_under_adamw(family):
    hlo = compiled_step_hlo(family)
    files = innermost_files(hlo)
    n = 0
    for line in hlo.splitlines():
        on, fr = _OP_NAME.search(line), _FRAME.search(line)
        if not (_INSTR.match(line) and on and fr):
            continue
        in_adamw = "adamw" in components(on.group(1))
        from_optimizer = files[int(fr.group(1))].endswith("optim/adamw.py")
        assert in_adamw == from_optimizer, (on.group(1), line[:120])
        n += from_optimizer
    assert n > 0


def host_events(trace_dir, names) -> list:
    """(name, start_ns, end_ns, stats) of the host events named in
    ``names`` in the trace written under ``trace_dir``."""
    path = next(trace_dir.rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in names:
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_supervisor_marks_each_step_and_its_spans(tmp_path):
    cfg = get_config("qwen1.5-0.5b").reduced(n_layers=1, d_model=32,
                                             d_ff=64, vocab=64)
    params = init(cfg, jax.random.PRNGKey(0))
    state = {"params": params, "opt": adamw_init(params),
             "step": jnp.zeros((), jnp.int32)}
    loader = TokenLoader(SyntheticTokenSource(cfg.vocab, seed=3),
                         batch=2, seq=8)
    sup = Supervisor(CheckpointManager(tmp_path / "ckpt", keep=1,
                                       async_save=False), loader,
                     checkpoint_every=1000)
    step = build_step(cfg, cosine_schedule(1e-3, 10))
    with jax.profiler.trace(str(tmp_path / "trace")):
        sup.run(state, step, n_steps=3, log_every=0)
    events = host_events(tmp_path / "trace", ("train_step", "data.block",
                                              "ft.sync", "ft.metrics"))
    steps = [e for e in events if e[0] == "train_step"]
    assert sorted(int(e[3]["step_num"]) for e in steps) == [1, 2, 3]
    for name in ("data.block", "ft.sync", "ft.metrics"):
        spans = [e for e in events if e[0] == name]
        assert len(spans) == 3, name
        for s in steps:
            inside = [x for x in spans if s[1] <= x[1] and x[2] <= s[2]]
            assert len(inside) == 1, (name, s[3])


def test_loader_opens_one_data_block_per_batch(tmp_path):
    loader = TokenLoader(SyntheticTokenSource(512, seed=7), batch=2,
                         seq=16)
    digests = []
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            fp = loader.fingerprint()
            b = loader.next_batch()
            digests.append((fp, hashlib.sha256(
                b["tokens"].tobytes() + b["labels"].tobytes()
            ).hexdigest()[:16]))
    assert len(host_events(tmp_path, ("data.block",))) == 3
    # the batches the loader gave before it was traced
    assert digests == [("b92e5e70c8dcc6b8", "193393a2929829bf"),
                       ("696bf6a1d3c437e7", "660881ee29402092"),
                       ("dfa739d60e771f97", "9bd8c44a839726e6")]
