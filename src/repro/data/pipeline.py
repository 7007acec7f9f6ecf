"""Token data pipeline: deterministic, shardable, exactly resumable.

Sources produce a (batch, seq+1) token block for a given global step;
``TokenLoader`` slices it into (tokens, labels), shards it per host, and
carries a checkpointable ``DataState`` so a restore resumes mid-epoch at
the exact same sample order (fault-tolerance requirement).
"""
from __future__ import annotations

import dataclasses
import hashlib
import pathlib
from dataclasses import dataclass
from typing import Optional

import numpy as np
from jax.profiler import TraceAnnotation


@dataclass
class DataState:
    step: int = 0
    epoch: int = 0
    seed: int = 0

    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_dict(d: dict) -> "DataState":
        return DataState(**d)


class SyntheticTokenSource:
    """Deterministic synthetic tokens: block(step) is a pure function of
    (seed, step) — identical across hosts, so each host slices its shard
    without communication.

    Sequences follow a noisy affine recurrence t_{n+1} = (a*t_n + c)
    mod V with flip probability ``noise`` — a learnable next-token
    structure, so training-loss decrease is a meaningful signal.  First
    tokens and flips are drawn by Zipf's law (token id r - 1 with
    probability proportional to 1/r), as words are in text: with
    uniform draws over a vocabulary of 150k nothing is learnable in a
    model's first steps, and the loss sits at ln V within noise."""

    def __init__(self, vocab: int, seed: int = 0,
                 noise: float = 0.15) -> None:
        self.vocab = vocab
        self.seed = seed
        self.noise = noise
        cdf = np.cumsum(1.0 / np.arange(1, vocab + 1))
        self._zipf_cdf = cdf / cdf[-1]

    def _zipf(self, rng, size) -> np.ndarray:
        ids = np.searchsorted(self._zipf_cdf, rng.random(size))
        return np.minimum(ids, self.vocab - 1).astype(np.int32)

    def block(self, step: int, batch: int, seq: int) -> np.ndarray:
        rng = np.random.Generator(np.random.Philox(
            key=self.seed, counter=[0, 0, 0, step]))
        v = self.vocab
        out = np.empty((batch, seq + 1), dtype=np.int32)
        out[:, 0] = self._zipf(rng, batch)
        flips = rng.random((batch, seq)) < self.noise
        rand = self._zipf(rng, (batch, seq))
        a, c = 5, 17
        for t in range(seq):
            nxt = (out[:, t] * a + c) % v
            out[:, t + 1] = np.where(flips[:, t], rand[:, t], nxt)
        return out


class MemmapTokenSource:
    """Flat binary token file (uint16/uint32).  Blocks are strided
    deterministically; wraps around at the end (epoch += 1)."""

    def __init__(self, path: str, vocab: int,
                 dtype: str = "uint16") -> None:
        self.path = pathlib.Path(path)
        self.vocab = vocab
        self.tokens = np.memmap(self.path, dtype=np.dtype(dtype),
                                mode="r")

    def block(self, step: int, batch: int, seq: int) -> np.ndarray:
        n = len(self.tokens)
        span = seq + 1
        out = np.empty((batch, span), dtype=np.int32)
        for i in range(batch):
            start = ((step * batch + i) * span) % max(n - span, 1)
            out[i] = self.tokens[start:start + span].astype(np.int32)
        return np.clip(out, 0, self.vocab - 1)


class SyntheticVectorSource:
    """Deterministic synthetic (x, y) regression batches for the
    annotated-MLP models the tests and benches train: ``block(step)`` is
    a pure function of (seed, step), and y is a fixed random linear map
    of x plus noise — learnable, so losses move and elastic-resume
    parity is a meaningful bit-level claim."""

    def __init__(self, d: int, seed: int = 0, noise: float = 0.1) -> None:
        self.d = d
        self.seed = seed
        self.noise = noise
        w_rng = np.random.Generator(np.random.Philox(
            key=seed, counter=[0, 0, 0, 0xE1A57]))
        self._w = w_rng.standard_normal((d, d)).astype(np.float32) \
            / np.sqrt(d)

    def block(self, step: int, batch: int) -> tuple[np.ndarray, np.ndarray]:
        rng = np.random.Generator(np.random.Philox(
            key=self.seed, counter=[0, 0, 1, step]))
        x = rng.standard_normal((batch, self.d)).astype(np.float32)
        eps = rng.standard_normal((batch, self.d)).astype(np.float32)
        y = np.tanh(x @ self._w) + self.noise * eps
        return x, y.astype(np.float32)


class VectorLoader:
    """``TokenLoader``'s sibling for (x, y) vector batches: same
    deterministic, host-shardable, exactly-resumable stream contract
    (``state_dict``/``load_state_dict``/``fingerprint``), so the elastic
    supervisor can checkpoint and restore its position."""

    def __init__(self, source: SyntheticVectorSource, batch: int,
                 host_id: int = 0, n_hosts: int = 1,
                 state: Optional[DataState] = None) -> None:
        assert batch % n_hosts == 0, (batch, n_hosts)
        self.source = source
        self.batch = batch
        self.host_id = host_id
        self.n_hosts = n_hosts
        self.state = state or DataState(seed=getattr(source, "seed", 0))

    def next_batch(self) -> dict:
        x, y = self.source.block(self.state.step, self.batch)
        per = self.batch // self.n_hosts
        sl = slice(self.host_id * per, (self.host_id + 1) * per)
        self.state.step += 1
        return {"x": x[sl].copy(), "y": y[sl].copy()}

    def state_dict(self) -> dict:
        return self.state.to_dict()

    def load_state_dict(self, d: dict) -> None:
        self.state = DataState.from_dict(d)

    def fingerprint(self) -> str:
        x, y = self.source.block(self.state.step, self.batch)
        return hashlib.sha256(x.tobytes() + y.tobytes()).hexdigest()[:16]


class TokenLoader:
    def __init__(self, source, batch: int, seq: int,
                 host_id: int = 0, n_hosts: int = 1,
                 state: Optional[DataState] = None) -> None:
        assert batch % n_hosts == 0, (batch, n_hosts)
        self.source = source
        self.batch = batch
        self.seq = seq
        self.host_id = host_id
        self.n_hosts = n_hosts
        self.state = state or DataState(seed=getattr(source, "seed", 0))

    def next_batch(self) -> dict:
        """The next (tokens, labels) batch; the source's work is the
        profiler span ``data.block``."""
        with TraceAnnotation("data.block"):
            blk = self.source.block(self.state.step, self.batch, self.seq)
        per = self.batch // self.n_hosts
        mine = blk[self.host_id * per:(self.host_id + 1) * per]
        self.state.step += 1
        return {"tokens": mine[:, :-1].copy(),
                "labels": mine[:, 1:].copy()}

    # -- checkpointing --------------------------------------------------
    def state_dict(self) -> dict:
        return self.state.to_dict()

    def load_state_dict(self, d: dict) -> None:
        self.state = DataState.from_dict(d)

    def fingerprint(self) -> str:
        """Digest of the next batch — used by resume tests to prove
        exact continuation."""
        blk = self.source.block(self.state.step, self.batch, self.seq)
        return hashlib.sha256(blk.tobytes()).hexdigest()[:16]
