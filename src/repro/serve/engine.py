"""Continuous-batching serving engine on paged KV caches.

Requests enter a FIFO queue and are admitted onto fixed batch *slots*
independently: each slot prefills its own prompt (in chunks, interleaved
with other slots' decode steps) and decodes at its own position, and a
finished slot is recycled immediately without touching its neighbors --
no wave barrier.  The device-side state is one jitted paged serve step
(:func:`repro.train.step.make_paged_serve_step`): KV lives in fixed-size
blocks indexed by a host-managed block table
(:class:`repro.serve.kv.KVBlockManager`), so slot recycling is a table
update, never a cache copy.

Every tick runs ONE step of shape ``(B, S)`` with per-row valid counts
``n_new``: prefilling rows carry up to ``prefill_chunk`` prompt tokens,
decoding rows carry their 1 pending token, idle rows carry 0.  S stays
in {1, prefill_chunk} so the program compiles at most twice.  Recurrent
archs (rglru / xLSTM) cannot mask inside a chunk, so for them ticks are
*aligned*: a row joins a chunk tick only with a full chunk (its prompt
tail runs at S=1) and decode rows only join S=1 ticks.

Tensor-parallel decode runs its psum / vocab-gather on ExecPlan
collectives picked by ``autotune.choose()`` at the decode message sizes
(``decode_collectives="plan"``, the default) -- the r = max_r /
traff_rounds latency regime that is the paper's headline result.  With a
measured tuning table attached (``tuning=True`` +
``REPRO_TUNING_CACHE``), the trace-time picks report
``source="measured"``; inspect them via :attr:`Engine.decode_choices`.

Sampling draws its randomness per ``(seed, request uid, token index)``
(Gumbel-max over the logits), never per slot or batch.  The logits
themselves are not promised bit-stable across batches: a request's rows
are computed at batch and chunk shapes that depend on what else is being
served, and the compiler may order a reduction differently at another
shape (bf16 activations on the chip make that rounding coarse).  So
where two tokens score within rounding of each other, a request may take
a different token, and then a different continuation, beside other
requests than alone.  The contract is checked against the model's plain
full forward instead: every served token scores within a stated
tolerance of the reference's best
(:func:`repro.serve.reference.served_token_margins`).
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field, replace
from typing import Callable, Deque, List, Optional

import jax.numpy as jnp
import numpy as np

from repro.models.attention import PageCtx
from repro.models.config import ModelConfig
from repro.models.model import init_paged_caches
from repro.obs import trace as obs_trace
from repro.obs.metrics import Histogram
from repro.parallel.api import (ParallelConfig, decode_choice_log,
                                reset_decode_choice_log)
from repro.serve.kv import KVBlockManager
from repro.serve.reference import gumbel_noise
from repro.train.step import make_paged_serve_step

_RECURRENT = ("rglru", "mlstm", "slstm")


def _now_us() -> float:
    return time.perf_counter_ns() / 1e3


@dataclass
class Request:
    prompt: np.ndarray              # (S,) int32
    max_new_tokens: int = 16
    out_tokens: List[int] = field(default_factory=list)
    done: bool = False
    # called as stream(request, token) on every generated token
    stream: Optional[Callable[["Request", int], None]] = None
    uid: Optional[int] = None       # assigned at submit (sampling key)
    # lifecycle timestamps (microseconds, perf_counter epoch), recorded
    # unconditionally -- latency accounting must not require tracing on
    t_enqueue_us: Optional[float] = None
    t_first_token_us: Optional[float] = None
    t_done_us: Optional[float] = None

    @property
    def ttft_us(self) -> Optional[float]:
        """Enqueue -> first generated token."""
        if self.t_enqueue_us is None or self.t_first_token_us is None:
            return None
        return self.t_first_token_us - self.t_enqueue_us

    @property
    def latency_us(self) -> Optional[float]:
        """Enqueue -> done."""
        if self.t_enqueue_us is None or self.t_done_us is None:
            return None
        return self.t_done_us - self.t_enqueue_us


@dataclass
class _Slot:
    """One live request's device-side coordinates."""
    req: Request
    fed: int = 0          # tokens written to cache/state so far
    next_tok: int = -1    # pending decode input (last sampled token)
    fresh: bool = True    # recurrent-state reset pending (first tick)

    @property
    def prefilling(self) -> bool:
        return self.fed < len(self.req.prompt)


class Engine:
    def __init__(self, cfg: ModelConfig, pc: ParallelConfig, mesh, params, *,
                 batch_slots: int = 4, max_len: int = 256,
                 prefill_chunk: int = 32, block_size: int = 16,
                 n_blocks: Optional[int] = None,
                 temperature: float = 0.0, seed: int = 0,
                 tuning: Optional[bool] = None,
                 decode_collectives: str = "plan",
                 bundle=None):
        """``batch_slots`` / ``n_blocks`` are PER DP SHARD; the global
        batch is ``batch_slots * dp``.  ``n_blocks`` defaults to full
        residency (every slot can hold ``max_len`` tokens) + the garbage
        block; pass less to exercise admission under block pressure.
        ``tuning`` / ``decode_collectives`` override the matching
        ParallelConfig fields without rebuilding it at call sites.
        ``bundle``: inject a prebuilt ``make_paged_serve_step`` result
        to share one compiled program across engines (tests)."""
        if tuning is not None and tuning != pc.tuning:
            pc = replace(pc, tuning=tuning)
        if decode_collectives != pc.decode_collectives:
            pc = replace(pc, decode_collectives=decode_collectives)
        self.cfg, self.pc, self.mesh = cfg, pc, mesh
        self.params = params
        self.dp = max(pc.dp, 1)
        self.slots_per_shard = batch_slots
        self.B = batch_slots * self.dp
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.block_size = block_size
        self.temperature = temperature
        self.seed = seed
        # recurrent rows cannot mask mid-chunk: aligned tick scheduling
        self.aligned = any(k in _RECURRENT for k in cfg.blocks)
        self.nb_max = -(-max_len // block_size)
        if n_blocks is None:
            n_blocks = 1 + batch_slots * self.nb_max
        self.n_blocks = n_blocks
        self.kv = [KVBlockManager(n_blocks, block_size, self.nb_max,
                                  batch_slots) for _ in range(self.dp)]
        if bundle is None:
            # fresh compile session: picks logged at trace time belong
            # to this bundle.  An injected bundle keeps its log -- its
            # programs (and their choices) predate this engine.
            reset_decode_choice_log()
            bundle = make_paged_serve_step(cfg, pc, mesh)
        self.bundle = bundle
        self.caches = init_paged_caches(cfg, pc, self.B,
                                        n_blocks * self.dp, block_size)
        self.lengths = np.zeros(self.B, np.int32)
        self.slots: List[Optional[_Slot]] = [None] * self.B
        self.queue: Deque[Request] = deque()
        self._next_uid = 0
        # always-on request accounting (tracing adds spans on top)
        self._ttft = Histogram("ttft_us")
        self._latency = Histogram("request_latency_us")
        self._n_requests = 0
        self._n_tokens = 0
        self._n_ticks = 0
        self._n_prefill_ticks = 0

    # ------------------------------------------------------------ queue
    def submit(self, req: Request) -> Request:
        """Enqueue one request (FIFO).  Returns it with ``uid`` set."""
        total = len(req.prompt) + req.max_new_tokens
        if total > self.max_len:
            raise ValueError(f"prompt+max_new={total} exceeds "
                             f"max_len={self.max_len}")
        if req.t_enqueue_us is None:
            req.t_enqueue_us = _now_us()
        if req.uid is None:
            req.uid = self._next_uid
            self._next_uid += 1
        self.queue.append(req)
        self._n_requests += 1
        return req

    def _admit(self) -> None:
        """Strict-FIFO admission: the queue head is admitted to the first
        shard with a free slot AND room for its full block footprint;
        if the head cannot be placed, nothing behind it jumps ahead."""
        while self.queue:
            req = self.queue[0]
            need = len(req.prompt) + req.max_new_tokens
            placed = False
            for shard in range(self.dp):
                if not self.kv[shard].fits(need):
                    continue
                base = shard * self.slots_per_shard
                for local in range(self.slots_per_shard):
                    b = base + local
                    if self.slots[b] is None:
                        self.kv[shard].admit(local, need)
                        self.slots[b] = _Slot(req=req)
                        self.lengths[b] = 0
                        placed = True
                        break
                if placed:
                    break
            if not placed:
                return
            self.queue.popleft()

    # ------------------------------------------------------------ ticking
    def _plan_tick(self):
        """Pick this tick's S and per-row (tokens, n_new)."""
        chunk = self.prefill_chunk
        if self.aligned:
            # chunk ticks carry ONLY rows with >= chunk prompt tokens left
            full = [b for b, s in enumerate(self.slots)
                    if s is not None
                    and len(s.req.prompt) - s.fed >= chunk]
            if full:
                return chunk, full
            live = [b for b, s in enumerate(self.slots) if s is not None]
            return 1, live
        any_prefill = any(s is not None and s.prefilling
                          for s in self.slots)
        live = [b for b, s in enumerate(self.slots) if s is not None]
        return (chunk if any_prefill else 1), live

    def step(self) -> int:
        """Admit + run one device tick.  Returns #tokens generated.

        The tick is one ``engine.tick`` span over leaf spans in order:
        ``engine.admit``, ``engine.build`` (plan, host arrays, uploads),
        ``engine.dispatch`` (the serve step), ``engine.fetch`` (the
        logits to the host; only on ticks that emit a token) and
        ``engine.sample`` (sampling, streams, retiring).  Its args are
        the tick's counters (see ``repro.obs.trace``: computed only
        while a sink is on)."""
        span = obs_trace.span
        with span("engine.tick", cat="serve") as tick:
            with span("engine.admit", cat="serve"):
                self._admit()
            if all(s is None for s in self.slots):
                return 0
            with span("engine.build", cat="serve"):
                S, rows, toks, n_new, emit, ctx = self._build_tick()
            if tick is not obs_trace._NULL_SPAN:
                tokens = int(n_new.sum())
                tick.set(s=S, rows=len(rows), queued=len(self.queue),
                         tokens=tokens, pad_slots=self.B * S - tokens,
                         kv_blocks_used=sum(m.n_used for m in self.kv),
                         kv_tokens=int((self.lengths + n_new).sum()),
                         attn_pairs=int((n_new * self.lengths.astype(np.int64)
                                         + n_new * (n_new + 1) // 2).sum()))
            with span("engine.dispatch", cat="serve"):
                logits, self.caches = self.bundle.serve_step(
                    self.params, toks, self.caches, ctx)
            lg = None
            if emit:
                with span("engine.fetch", cat="serve"):
                    lg = np.asarray(logits[:, 0], np.float32)
            with span("engine.sample", cat="serve"):
                self._retire_tick(S, rows, n_new, emit, lg)
        return len(emit)

    def _build_tick(self):
        """Plan one tick and upload its inputs.  Returns (S, rows,
        tokens, n_new, rows that emit a token this tick, page context)."""
        S, rows = self._plan_tick()
        toks = np.zeros((self.B, S), np.int32)
        n_new = np.zeros(self.B, np.int32)
        reset = np.zeros(self.B, bool)
        emit = []
        for b in rows:
            s = self.slots[b]
            if s.prefilling:
                n = min(S, len(s.req.prompt) - s.fed)
                toks[b, :n] = s.req.prompt[s.fed:s.fed + n]
            else:
                n = 1
                toks[b, 0] = s.next_tok
            n_new[b] = n
            reset[b] = s.fresh
            s.fresh = False
            # mid-prefill rows' logits are not meaningful yet
            if s.fed + n >= len(s.req.prompt) + len(s.req.out_tokens):
                emit.append(b)
        table = np.concatenate([m.table for m in self.kv], axis=0)
        ctx = PageCtx(block_table=jnp.asarray(table),
                      # a host copy: on the CPU backend the device array
                      # may alias the numpy buffer (jnp.array's own copy
                      # runs asynchronously, reading it late), and this
                      # loop updates self.lengths in place while the
                      # step may still be running
                      lengths=jnp.asarray(self.lengths.copy()),
                      n_new=jnp.asarray(n_new),
                      reset=jnp.asarray(reset))
        return S, rows, jnp.asarray(toks), n_new, emit, ctx

    def _retire_tick(self, S: int, rows, n_new, emit, lg) -> None:
        """Book the tick's fed tokens, then sample each emitting row's
        token from the fetched logits ``lg``, stream it, and retire the
        rows that are done."""
        self._n_ticks += 1
        self._n_prefill_ticks += int(S > 1)
        self.lengths += n_new
        for b in rows:
            self.slots[b].fed += int(n_new[b])
        for b in emit:
            s = self.slots[b]
            tok = self._sample(lg[b], s.req.uid, len(s.req.out_tokens))
            s.req.out_tokens.append(tok)
            s.next_tok = tok
            self._n_tokens += 1
            now = _now_us()
            if s.req.t_first_token_us is None:
                s.req.t_first_token_us = now
                if s.req.ttft_us is not None:
                    self._ttft.record(s.req.ttft_us)
            if s.req.stream is not None:
                s.req.stream(s.req, tok)
            if len(s.req.out_tokens) >= s.req.max_new_tokens:
                s.req.done = True
                s.req.t_done_us = now
                if s.req.latency_us is not None:
                    self._latency.record(s.req.latency_us)
                shard, local = divmod(b, self.slots_per_shard)
                self.kv[shard].retire(local)
                self.slots[b] = None
                self.lengths[b] = 0

    def run(self) -> None:
        """Drive ticks until queue and slots drain."""
        while self.queue or any(s is not None for s in self.slots):
            self.step()

    def generate(self, requests: List[Request]) -> List[Request]:
        """Submit a batch and serve it to completion (offline mode)."""
        for r in requests:
            self.submit(r)
        self.run()
        return requests

    # ------------------------------------------------------------ sampling
    def _sample(self, logits_row: np.ndarray, uid: int, step: int) -> int:
        """Greedy argmax, or Gumbel-max at ``temperature`` keyed by
        (seed, uid, step): one vectorized argmax over the vocab, and the
        draw depends only on the request identity -- not on its slot,
        admission order, or batch mates."""
        if self.temperature <= 0:
            return int(logits_row.argmax())
        gumbel = gumbel_noise(self.seed, uid, step, logits_row.shape[-1])
        return int((logits_row / self.temperature + gumbel).argmax())

    # ------------------------------------------------------------ stats
    @property
    def decode_choices(self):
        """Trace-time decode collective picks: [(op, nbytes, Choice)]."""
        return decode_choice_log()

    def stats(self) -> dict:
        """Always-on serving statistics (independent of tracing).

        ``ttft_us`` / ``request_latency_us`` are enqueue -> first-token
        and enqueue -> done distributions (count/mean/p50/p90/p99) over
        every finished request; ``tokens`` counts generated tokens;
        ``ticks`` counts device steps (``prefill_ticks`` of them at
        S = prefill_chunk).  The dict is plain JSON, merged into the
        metrics snapshot by the serving benchmarks.
        """
        return {
            "requests": self._n_requests,
            "tokens": self._n_tokens,
            "ticks": self._n_ticks,
            "prefill_ticks": self._n_prefill_ticks,
            "queued": len(self.queue),
            "live": sum(s is not None for s in self.slots),
            "kv": [m.stats() for m in self.kv],
            "ttft_us": self._ttft.summary(),
            "request_latency_us": self._latency.summary(),
        }
