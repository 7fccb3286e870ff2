"""Continuous-batching autoregressive serving (the LLM decode plane).

The ServingEngine batches fixed-shape `run_batch` calls: right for
ResNet/OCR, wrong for decoders, where per-request full-sequence recompute
wastes nearly all decode FLOPs and fixed batches idle between stragglers.

- **The model declares its cache; the engine owns a pool of it.** A model
  answers three names and the engine asks nothing else of it:
  `init_cache(batch, max_len, dtype)` -> a flat list of arrays with the
  sequence on axis 0; `forward_cached(tokens, cache, positions,
  lengths=None)` -> `(logits [B, vocab] of each row's last real position,
  new cache)`, with `lengths` reading prompts `[B, T]` right-padded to T
  from an empty cache, without it one token a row (`[B, 1]`) through
  `cache`; `cache_tag`, the pool's tag in the memory census (`kv_pool`:
  `GPTForCausalLM`'s K/V pages, `models/gpt.py`; `state_pool`:
  `BrumbyForCausalLM`'s recurrent state, `models/brumby.py`, where
  `llm.decode.state_bytes` counts what a step rewrites), or a tuple of
  them, one tag an array of `init_cache`'s list, for a model that keeps
  both kinds (`LingForCausalLM`, `models/ling.py`: a recurrent state and
  a convolution's rows per linear-attention layer, a latent page per
  attention layer): each group is tagged and counted by its own rule.
  `forward_cached` may return MORE arrays than `init_cache` gave: the
  first `len(init_cache(...))` are the cache, what follows is the model's
  own report of the call (`LingForCausalLM`: the experts every routed
  layer chose, a row and position), which both programs hand out after
  the cache and the engine neither keeps nor reads.
  The engine builds the list once with `batch = num_slots` (the pool)
  and hands ALL of it donated (`to_static(..., donate_inputs=...)`) to
  the two programs that change it, `jit_llm_decode` and
  `jit_llm_slot_write`: each updates the buffers it was given and aliases
  them out (`self._pool` is the program's outputs from the moment the
  dispatch returns; `llm.decode.pool_donated` and
  `llm.slot_write.donated` count the steps and the admissions that gave
  the old ones away). The engine never looks inside an array.
- **Three kinds of program, fixed shapes.** Sequences borrow a slot for
  their lifetime; shapes never depend on which slots are live, so steady
  state runs one prefill executable per length bucket, one slot write
  (every array's row block at `slot`, which is data) and one decode
  executable, with ZERO steady-state compiles (the `jit.*` retrace
  counters stay flat). `llm.prefill.tokens_real` / `tokens_bucket`: padding.
  A slot without a sequence still has a row in the step, and what the
  model is told of it is its position: **a step's row at position 0
  carries no sequence and its output is unspecified.** An empty prompt is
  refused (`submit`), so a live row's position is at least 1, and
  `_dispatch` leaves the position of a free slot and of a row it leaves
  out 0. A model may spend nothing on such a row: the page reads
  (`decode_attention`, `mla_decode`) read a block of it, the routed models
  send it to no expert (`nn.RoutedExperts(live=)`;
  `llm.decode.rows_dead` counts those rows beside `llm.decode.rows`), and
  a prompt's rows past its `lengths` are the same to them.
- **Continuous scheduler, one decode step ahead.** Every turn admits
  queued sequences into free slots and evicts on EOS/length/deadline,
  streaming each token to the caller the moment the host holds it (and
  over the wire as `'PDST'` frames via `inference/server.py`). Admission
  sheds on SLO burn (`obs/slo.py`) and queue depth, like the batch
  engine. All that step n+1 needs of step n is its `[S]` greedy tokens,
  which `jit_llm_decode` computes itself (`outs[0]`); positions the host
  knows without reading anything. So a turn (`_step`) DISPATCHES step
  n+1 on step n's `outs[0]`, still on the device and never donated, and
  only THEN reads step n, emits and evicts: dispatch, read and emit run
  while the chip runs, not between two programs. The depth is exactly
  one step and not a setting. What the host knows ahead it uses: a
  sequence whose budget or page ends with the step in flight is left out
  of the next one (its row is at position 0, as a free slot's is:
  above). EOS and a deadline are learnt at the read: that sequence has one
  more row in the step already in flight, whose token is discarded,
  never emitted (`llm.decode.discarded`), and its slot is free at once
  (the next prefill's slot write depends on the pool the step in flight
  returns, so it lands after it). An admission first reads and emits the
  step in flight (`llm.decode.drains`), so no finished token waits for a
  prefill, and every live slot's next token is then on the host: the
  first step after an empty pipeline takes host tokens, every other one
  the device's (`llm.decode.ahead`), and no step mixes the two.
- **Quantized decode arm.** `LLMConfig(quant="int8")` runs the decoder
  matmuls through `quantization.quant_weight_only`; `kv_int8=True` asks a
  model that keeps K/V pages for an int8 cache.

Reference parity: this is the Paddle-Serving deployment role (PAPER.md
§1 row 8) taken to continuous batching over a paged KV cache: the
vLLM-style iteration-level scheduler, built TPU-first (fixed shapes, three
kinds of executable, no steady-state compiles) instead of kernel-first.
"""
from __future__ import annotations

import collections
import queue
import threading
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from .. import faults as _faults
from .. import monitor as _monitor
from .. import nn
from ..core import executable as _exe
from ..core.autograd import no_grad
from ..core.tensor import Tensor
from ..obs import memory as _mem
from ..obs import slo as _slo
from .engine import (
    DeadlineExceededError, EngineStoppedError, ServerOverloadedError,
    ServingError,
)
from ..utils import syncwatch as _syncwatch

__all__ = ["LLMConfig", "LLMEngine", "LLMStream"]


def _prefill_ladder(max_len: int, declared: Sequence[int] = ()) -> List[int]:
    """Prefill length buckets: declared ones (clamped to max_len), or
    powers of two from 8 up to max_len. One cached executable each."""
    if declared:
        ladder = sorted({int(b) for b in declared if 0 < int(b) <= max_len})
        if ladder:
            return ladder
    ladder, b = [], 8
    while b < max_len:
        ladder.append(b)
        b *= 2
    ladder.append(max_len)
    return sorted(set(ladder))


@dataclass
class LLMConfig:
    """Knobs for the continuous-batching engine.

    Pool sizing recipe, K/V pages (`GPTForCausalLM`): bytes = 2 (K and V)
    * num_layers * num_slots * (max_len + 2, `models.ernie.DECODE_BLOCK`)
    * heads * head_dim * itemsize: fp32 itemsize 4, kv_int8 itemsize 1 (+
    two f32 scales per slot per layer). A recurrent state
    (`BrumbyForCausalLM`): bytes = num_layers *
    num_slots * kv_heads * rows * (head_dim + 1) * 4 with rows =
    head_dim (head_dim + 1) / 2 rounded up to 128 — 0.275 GB a slot at
    Brumby-14B's widths and 8 layers, whatever `max_len` is (which only
    bounds a sequence's positions there). `LLMEngine.kv_pool_bytes()`
    reports the real figure of either and the census publishes it as
    `mem.kv_pool.bytes` / `mem.state_pool.bytes`. A mixed pool
    (`LingForCausalLM`): bytes a slot = the parts that do not grow
    (per linear-attention layer heads * head_dim^2 * 4 of state and 3 *
    3 * heads * head_dim * itemsize of convolution rows) + the page
    (attention layers * max_len * (latent + rope) * itemsize); each
    group is published under its own tag. That is what the pool
    costs on the device: the decode step and the slot write take it
    donated, so beside the weights a deployment budgets the pool once
    (the TPU pads a page's position axis to its tile of 8 rows in fp32:
    1026 positions occupy 1032), plus one sequence's fresh cache between
    an admission's prefill and its write."""

    num_slots: int = 8
    max_len: int = 256
    prefill_buckets: Tuple[int, ...] = ()
    max_new_tokens: int = 64
    eos_token_id: Optional[int] = None
    queue_depth: int = 256
    default_deadline_ms: Optional[float] = None
    warmup_on_start: bool = True
    quant: str = "off"          # "off" | "int8" weight-only decoder matmuls
    kv_int8: bool = False
    idle_park_s: float = 0.02   # scheduler nap when no work is queued


class LLMStream:
    """Per-request handle: tokens stream into it as the scheduler emits
    them; iterate to consume incrementally, or `result()` to wait for the
    terminal status. Terminal statuses: "done" (EOS or token budget),
    "deadline", "error" (injected/model fault), "stopped" (engine shut
    down before completion)."""

    def __init__(self, request_id: int, on_token: Optional[Callable] = None):
        self.request_id = request_id
        self.tokens: List[int] = []
        self.status = "queued"
        self.error: Optional[str] = None
        self._on_token = on_token
        self._q: "queue.Queue" = queue.Queue()
        self._done = threading.Event()

    # scheduler-side
    def _emit(self, tok: int) -> None:
        self.tokens.append(tok)
        self._q.put(tok)
        if self._on_token is not None:
            try:
                self._on_token(len(self.tokens) - 1, tok)
            except Exception:
                pass  # a broken callback must not kill the scheduler

    def _finish(self, status: str, error: Optional[str] = None) -> None:
        if self._done.is_set():
            return
        self.status = status
        self.error = error
        self._done.set()
        self._q.put(None)

    # consumer-side
    def __iter__(self):
        return self.iter()

    def iter(self, timeout: Optional[float] = 600.0):
        """Yield tokens as they arrive until the stream terminates."""
        while True:
            tok = self._q.get(timeout=timeout)
            if tok is None:
                return
            yield tok

    def result(self, timeout: Optional[float] = None) -> Tuple[str, List[int]]:
        """(terminal status, all tokens); raises TimeoutError on wait."""
        if not self._done.wait(timeout):
            raise TimeoutError("generation still in flight")
        return self.status, list(self.tokens)

    def done(self) -> bool:
        return self._done.is_set()


@dataclass
class _Seq:
    stream: LLMStream
    prompt: np.ndarray
    max_new: int
    deadline: Optional[float]          # absolute monotonic, or None
    submit_t: float
    slot: int = -1
    # tokens cached once the step in flight has landed: the position of
    # the next row to DISPATCH (what has been emitted is `stream.tokens`)
    pos: int = 0
    last_token: int = 0                # the last token emitted
    last_emit_t: float = 0.0
    admit_t: float = 0.0

    @property
    def sent(self) -> int:
        """The tokens its stream will hold once the step in flight is
        read: the prefill's one and a token a row dispatched."""
        return self.pos - int(self.prompt.size) + 1

    def ends_at(self, n_tokens: int, max_len: int) -> bool:
        """Whether its `n_tokens`-th token is its last: the budget is
        spent, or the page's last position is cached."""
        return (n_tokens >= self.max_new
                or int(self.prompt.size) + n_tokens - 1 >= max_len)


class _PrefillNet(nn.Layer):
    """One prefill executable per length bucket: (tokens [B, Lb],
    lengths [B]) -> (first greedy token [B], last-position logits [B, V],
    the fresh cache). The cache is created inside the trace so the wire
    signature is just the token block."""

    def __init__(self, lm, max_len: int, dtype: str):
        super().__init__()
        self.lm = lm
        self._max_len = max_len
        self._dtype = dtype

    def forward(self, tokens, lengths):
        from ..ops.creation import zeros
        from ..ops.manipulation import cast
        from ..ops.search import argmax

        b = tokens.shape[0]
        cache = self.lm.init_cache(b, self._max_len, dtype=self._dtype)
        last, cache = self.lm.forward_cached(
            tokens, cache, zeros([b], dtype="int32"), lengths)
        return (cast(argmax(last, axis=-1), "int32"), last, *cache)


class _DecodeNet(nn.Layer):
    """THE decode executable: one fixed-shape step for the whole pool.
    (tokens [S], positions [S], *pool) -> (next greedy token [S], logits
    [S, V], the updated pool). Occupancy never changes the signature: a
    free slot has a row, at position 0, which carries no sequence and
    whose outputs are unspecified (module docstring), so the model may
    leave its work out (the page reads and the routed experts do)."""

    def __init__(self, lm):
        super().__init__()
        self.lm = lm

    def forward(self, tokens, positions, *cache):
        from ..ops.manipulation import cast, unsqueeze
        from ..ops.search import argmax

        last, cache = self.lm.forward_cached(unsqueeze(tokens, 1),
                                             list(cache), positions)
        return (cast(argmax(last, axis=-1), "int32"), last, *cache)


class _SlotWriteNet(nn.Layer):
    """THE slot write of an admission: (slot [] int32, *pool, *rows) ->
    the pool with every array's row block at `slot` replaced by its
    `rows` array (a prefill's fresh cache, `[1, ...]` each), one
    `dynamic_update_slice` on axis 0 an array. `slot` is data: one
    executable serves every slot and every prefill bucket."""

    def forward(self, slot, *arrays):
        import jax

        from ..ops._dispatch import nondiff_op

        n = len(arrays) // 2

        def write(s, *a):
            return tuple(jax.lax.dynamic_update_slice(
                pool, row, (s,) + (0,) * (pool.ndim - 1))
                for pool, row in zip(a[:n], a[n:]))

        return nondiff_op(write, [slot, *arrays])


class LLMEngine:
    """Continuous-batching scheduler over a slot-paged pool of whatever
    the model keeps a sequence: K/V pages or a recurrent state.

    `submit()` is thread-safe and returns an `LLMStream` immediately; a
    single scheduler thread owns the pool and runs the admit -> decode ->
    evict loop. See LLMConfig for sizing and the module docstring for the
    executable-count invariant."""

    _FAULT_SITE = "llm.decode"

    def __init__(self, model, config: Optional[LLMConfig] = None):
        cfg = config or LLMConfig()
        self.config = cfg
        self.lm = model
        if not all(hasattr(model, a) for a in
                   ("init_cache", "forward_cached", "cache_tag")):
            raise ServingError(
                "LLMEngine needs a model that declares its cache: "
                "init_cache, forward_cached and cache_tag (module "
                "docstring; GPTForCausalLM, BrumbyForCausalLM)")
        if cfg.kv_int8 and model.cache_tag != "kv_pool":
            raise ServingError("kv_int8 quantises K/V pages; this model "
                               f"keeps a {model.cache_tag}")
        self.lm.eval()  # serving path: dropout off, rng-stable
        if cfg.quant == "int8":
            from ..quantization import quant_weight_only
            quant_weight_only(self.lm)
        elif cfg.quant not in ("", "off"):
            raise ServingError(f"unknown llm quant arm {cfg.quant!r}")
        self._dtype = "int8" if cfg.kv_int8 else "float32"
        self.buckets = _prefill_ladder(cfg.max_len, cfg.prefill_buckets)

        # what the model says a sequence keeps, slot on axis 0
        self._pool: List[Tensor] = self._zero_pool()
        # one tag an array; a string stands for every array
        tags = model.cache_tag
        self._tags: Tuple[str, ...] = ((tags,) * len(self._pool)
                                       if isinstance(tags, str)
                                       else tuple(tags))
        if len(self._tags) != len(self._pool) or \
                set(self._tags) - {"kv_pool", "state_pool"}:
            raise ServingError(
                f"cache_tag {model.cache_tag!r} does not tag the "
                f"{len(self._pool)} arrays of init_cache as kv_pool or "
                "state_pool")
        self._prefill = _PrefillNet(model, cfg.max_len, self._dtype)
        self._decode = _DecodeNet(model)
        self._slot_write = _SlotWriteNet()
        from ..jit import to_static
        # the programs' names in a trace: jit_llm_prefill, jit_llm_decode,
        # jit_llm_slot_write. The engine owns the pool, so it alone may
        # give it away: the decode program (its inputs after tokens and
        # positions) and the slot write (after the slot) take the pool
        # donated, write it in place and alias it out.
        to_static(self._prefill, name="llm_prefill")
        to_static(self._decode, name="llm_decode",
                  donate_inputs=slice(2, 2 + len(self._pool)))
        to_static(self._slot_write, name="llm_slot_write",
                  donate_inputs=slice(1, 1 + len(self._pool)))

        self._free: List[int] = list(range(cfg.num_slots))
        self._active: Dict[int, _Seq] = {}
        # the decode step dispatched and not yet read (the scheduler
        # thread's own): its tokens, still on the device, and the (slot,
        # sequence) rows it computes
        self._flight: Optional[Tuple[Tensor, List[Tuple[int, _Seq]]]] = None
        self._pending: "collections.deque[_Seq]" = collections.deque()
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._stopped = False
        self._thread: Optional[threading.Thread] = None
        self._next_id = 0
        self._counters = {"requests": 0, "completed": 0, "shed": 0,
                          "evictions.eos": 0, "evictions.length": 0,
                          "evictions.deadline": 0, "evictions.error": 0}
        self._warm_ms = 0.0

    def _zero_pool(self) -> List[Tensor]:
        cfg = self.config
        return list(self.lm.init_cache(cfg.num_slots, cfg.max_len,
                                       dtype=self._dtype))

    def _run_on_pool(self, program, head, tail=(), at=0):
        """Run `program` on (*head, *pool, *tail). It consumes the pool:
        `self._pool` is its output arrays (from `at` on) from the moment
        the dispatch returns, so no other thread and no later line ever
        holds a deleted array. Returns (outs, donated): donated is whether
        the old buffers were really given away (a host attribute read).
        Nothing here waits for the device. A dispatch that fails after
        taking the pool leaves a zero pool behind; every sequence is lost
        with it either way."""
        old = self._pool[0]._value
        try:
            outs = program(*head, *self._pool, *tail)
        except BaseException:
            if any(t._value.is_deleted() for t in self._pool):
                self._pool = self._zero_pool()
            raise
        self._pool = list(outs[at:at + len(self._pool)])
        return outs, old.is_deleted()

    def _decode_pool(self, tokens, positions):
        """The decode program on (tokens, positions) and the pool
        (`_run_on_pool`). `tokens` is a host array, or a `Tensor` that is
        handed on as it is: an earlier step's `outs[0]`, still on the
        device and possibly not computed yet (it is never donated, so the
        caller may read it afterwards). Returns (outs, donated): outs[0]
        the greedy tokens, outs[1] the logits, then the pool, then
        whatever else the model reports."""
        import jax.numpy as jnp
        if not isinstance(tokens, Tensor):
            tokens = Tensor(jnp.asarray(tokens))
        return self._run_on_pool(
            self._decode, (tokens, Tensor(jnp.asarray(positions))), at=2)

    def _write_slot(self, slot: int, rows: Sequence[Tensor]) -> bool:
        """The slot-write program on the pool (`_run_on_pool`): `rows`, a
        prefill's fresh cache, lands in `slot` of every array, in place.
        Returns whether the old buffers were given away."""
        import jax.numpy as jnp
        return self._run_on_pool(
            self._slot_write, (Tensor(jnp.asarray(slot, jnp.int32)),),
            tuple(rows))[1]

    # ---- lifecycle ---------------------------------------------------------

    def start(self) -> "LLMEngine":
        if self._thread is not None:
            return self
        if self.config.warmup_on_start:
            self._warmup()
        self._thread = _syncwatch.Thread(target=self._run, daemon=True,
                                        name="llm-scheduler")
        self._thread.start()
        return self

    def _warmup(self) -> None:
        """Trace+compile every prefill bucket, the slot write and the
        decode step up front so steady-state serving performs zero
        compiles."""
        import jax.numpy as jnp
        t0 = time.monotonic()
        with no_grad():
            for lb in self.buckets:
                fresh = self._prefill(Tensor(jnp.zeros((1, lb), jnp.int32)),
                                      Tensor(jnp.ones((1,), jnp.int32)))
            s = self.config.num_slots
            # the pool is zeros and no slot is live: what this writes into
            # slot 0, and the junk rows at position 0, are never read (an
            # admission replaces its slot whole). The decode step once on
            # host tokens and once on its own, the two ways it is fed
            self._write_slot(0, fresh[2:2 + len(self._pool)])
            outs, _ = self._decode_pool(np.zeros((s,), np.int32),
                                        np.zeros((s,), np.int32))
            self._decode_pool(outs[0], np.zeros((s,), np.int32))
        self._warm_ms = (time.monotonic() - t0) * 1000.0
        if _monitor._ENABLED:
            _monitor.gauge_set("llm.warm_start_ms", self._warm_ms)
            _monitor.count("llm.warmup_runs", len(self.buckets) + 3)

    def stop(self, drain: bool = True, timeout: float = 30.0) -> None:
        if drain and self._thread is not None:
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                with self._lock:
                    if not self._pending and not self._active \
                            and self._flight is None:
                        break
                time.sleep(0.01)
        with self._work:
            self._stopped = True
            self._work.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        with self._lock:
            leftovers = list(self._pending) + list(self._active.values())
            self._pending.clear()
            self._active.clear()
            self._free = list(range(self.config.num_slots))
        for seq in leftovers:
            seq.stream._finish("stopped", "engine stopped")
        # Break the StaticFunction <-> jax.jit reference cycle so the
        # model weights and KV pool become collectable once the engine
        # is dropped (the cycle runs through C-level jit wrappers the
        # garbage collector cannot traverse).
        for net in (self._prefill, self._decode, self._slot_write):
            fwd = getattr(net, "forward", None)
            if hasattr(fwd, "release"):
                fwd.release()

    # ---- submission --------------------------------------------------------

    def submit(self, prompt_ids: Sequence[int],
               max_new_tokens: Optional[int] = None,
               deadline_ms: Optional[float] = None,
               on_token: Optional[Callable] = None) -> LLMStream:
        """Queue one generation; returns its LLMStream immediately.
        Sheds with ServerOverloadedError on queue depth or SLO burn
        (`FLAGS_slo_shed_burn`), like ServingEngine.submit."""
        prompt = np.asarray(prompt_ids, dtype=np.int32).reshape(-1)
        if prompt.size == 0:
            raise ServingError("empty prompt")
        if prompt.size > self.buckets[-1]:
            raise ServingError(
                f"prompt length {prompt.size} exceeds the largest prefill "
                f"bucket {self.buckets[-1]} (raise LLMConfig.max_len)")
        if self._stopped or self._thread is None:
            raise EngineStoppedError("LLM engine not running")
        if _slo._ENABLED and _slo.should_shed():
            self._counters["shed"] += 1
            if _monitor._ENABLED:
                _monitor.count("llm.shed")
            _slo.record_request(None, _slo.OUTCOME_REJECTED)
            raise ServerOverloadedError("shedding on SLO burn rate")
        budget = self.config.max_len - int(prompt.size)
        max_new = min(int(max_new_tokens or self.config.max_new_tokens),
                      max(budget, 1))
        if deadline_ms is None:
            deadline_ms = self.config.default_deadline_ms
        now = time.monotonic()
        deadline = now + deadline_ms / 1000.0 if deadline_ms else None
        with self._work:
            if len(self._pending) >= self.config.queue_depth:
                self._counters["shed"] += 1
                if _monitor._ENABLED:
                    _monitor.count("llm.shed")
                raise ServerOverloadedError(
                    f"llm queue full ({self.config.queue_depth})")
            self._next_id += 1
            stream = LLMStream(self._next_id, on_token)
            seq = _Seq(stream=stream, prompt=prompt, max_new=max_new,
                       deadline=deadline, submit_t=now)
            self._pending.append(seq)
            self._counters["requests"] += 1
            self._work.notify()
        if _monitor._ENABLED:
            _monitor.count("llm.requests")
        return stream

    def generate(self, prompt_ids: Sequence[int],
                 max_new_tokens: Optional[int] = None,
                 deadline_ms: Optional[float] = None,
                 timeout: float = 600.0) -> List[int]:
        """Blocking convenience wrapper: submit + wait; raises the
        deadline/error terminal statuses as serving exceptions."""
        status, toks = self.submit(
            prompt_ids, max_new_tokens, deadline_ms).result(timeout)
        if status == "deadline":
            raise DeadlineExceededError("generation deadline exceeded")
        if status != "done":
            raise ServingError(f"generation {status}")
        return toks

    # ---- scheduler ---------------------------------------------------------

    def _run(self) -> None:
        with no_grad():
            while True:
                with self._work:
                    if self._stopped:
                        break
                    if not self._pending and not self._active \
                            and self._flight is None:
                        with _monitor.span("llm.park"):
                            self._work.wait(timeout=self.config.idle_park_s)
                        if self._stopped:
                            break
                    pending_now = bool(self._pending)
                if pending_now:
                    self._admit()
                if self._active or self._flight is not None:
                    self._turn()
            # a stop that did not wait: the step in flight is read and
            # emitted before the streams left are told "stopped"
            self._drain()

    def _turn(self, ahead: bool = True) -> None:
        """`_step` under its span; the scheduler survives what it raises,
        at the dispatch or at the (later) read: every sequence is lost,
        the engine and its pool serve on."""
        try:
            with _monitor.span("llm.step", slots=len(self._active)):
                self._step(ahead)
        except Exception as e:
            self._evict_all("error", f"{type(e).__name__}: {e}")

    def _drain(self) -> None:
        """Empty the pipeline: read and emit the step in flight and
        dispatch nothing. Every live sequence's next token is then on the
        host (`last_token`)."""
        if self._flight is None:
            return
        self._turn(ahead=False)
        if _monitor._ENABLED:
            _monitor.count("llm.decode.drains")

    def _next_admission(self) -> Optional[_Seq]:
        """The next pending sequence, moved into a free slot, or None when
        the queue or the pool has nothing to give. A sequence whose
        deadline passed in the queue is finished here, never admitted."""
        while True:
            with self._lock:
                if not self._free or not self._pending:
                    return None
                seq = self._pending.popleft()
                slot = self._free.pop()
            now = time.monotonic()
            if seq.deadline is not None and now > seq.deadline:
                with self._lock:
                    self._free.append(slot)
                self._finish(seq, "deadline", "expired before admission")
                continue
            seq.slot, seq.admit_t = slot, now
            return seq

    def _admit(self) -> None:
        with self._lock:
            if not self._free or not self._pending:
                return
        # finished tokens are not held for the length of an admission
        # (before a slot is taken: a read that fails frees them all)
        self._drain()
        seq = self._next_admission()
        if seq is None:
            return
        # one span per call that admits: the stall every stream sees
        with _monitor.span("llm.admit"):
            while seq is not None:
                try:
                    self._prefill_into(seq)
                except Exception as e:
                    # as for a step that raises (`_turn`): the write may
                    # have taken the pool, so every sequence is lost with
                    # this one, the engine and its pool serve on
                    with self._lock:
                        self._active[seq.slot] = seq
                    self._evict_all("error", f"{type(e).__name__}: {e}")
                seq = self._next_admission()

    def _prefill_slot(self, prompt: np.ndarray, slot: int, rid: int = 0):
        """The device's part of an admission: the prompt, right-padded to
        its bucket, through that bucket's program, and the cache it
        returns written into `slot` of the pool by ONE program that takes
        the pool donated (`_write_slot`). Returns (first greedy token,
        bucket, last-position logits [1, V], what the model reports
        beside its cache)."""
        import jax.numpy as jnp

        plen = int(prompt.size)
        lb = next(b for b in self.buckets if b >= plen)
        with _monitor.span("llm.prefill", request_id=rid, bucket=lb,
                           prompt_len=plen):
            padded = np.zeros((1, lb), np.int32)
            padded[0, :plen] = prompt
            outs = self._prefill(Tensor(jnp.asarray(padded)),
                                 Tensor(jnp.full((1,), plen, jnp.int32)))
            first = int(np.asarray(outs[0].numpy())[0])

        rows = outs[2:2 + len(self._pool)]
        with _monitor.span("llm.slot_write", request_id=rid,
                           writes=len(rows)):
            donated = self._write_slot(slot, rows)
        if donated and _monitor._ENABLED:
            _monitor.count("llm.slot_write.donated")
        return first, lb, outs[1], outs[2 + len(rows):]

    def _prefill_into(self, seq: _Seq) -> None:
        cfg = self.config
        plen = int(seq.prompt.size)
        first, lb, *_ = self._prefill_slot(seq.prompt, seq.slot,
                                           seq.stream.request_id)
        with _monitor.span("llm.emit"):
            now = time.monotonic()
            seq.pos = plen
            seq.last_token = first
            seq.last_emit_t = now
            seq.stream.status = "running"
            seq.stream._emit(first)
            with self._lock:
                self._active[seq.slot] = seq
            if _monitor._ENABLED:
                _monitor.count("llm.prefill.requests")
                _monitor.count("llm.prefill.tokens_real", plen)
                _monitor.count("llm.prefill.tokens_bucket", lb)
                _monitor.count("llm.tokens_generated")
                _monitor.observe("llm.queue_wait", seq.admit_t - seq.submit_t)
                _monitor.observe("llm.ttft_ms",
                                 (now - seq.submit_t) * 1000.0)
                _monitor.gauge_set("llm.slots_active", len(self._active))
            self._retag_pool()
            # a one-token budget (or instant EOS) finishes without decoding
            if first == cfg.eos_token_id:
                self._evict(seq, "eos")
            elif seq.ends_at(1, cfg.max_len):
                self._evict(seq, "length")

    def _step(self, ahead: bool = True) -> None:
        """One turn of the scheduler, which runs ONE decode step ahead of
        what it has read: dispatch step n+1 for the sequences that go on
        (`_dispatch`; not with `ahead` false, which is a drain), THEN read
        step n's tokens, emit them and evict (`_collect`), while the chip
        runs n+1. Fixed shapes — occupancy is data, not signature. If the
        dispatch raises, step n stays in `_flight` for `_evict_all`."""
        prev = self._flight
        self._flight = self._dispatch(prev) if ahead else None
        if prev is not None:
            self._collect(*prev)

    def _dispatch(self, prev):
        """Dispatch the next decode step and return it as a flight
        (tokens on the device, rows), or None when no sequence goes on.
        Its tokens are `prev`'s own, step n's `outs[0]` handed on unread,
        or, with nothing in flight, the host's `last_token`s: the two
        never mix, since an admission drains the pipeline first and so
        every sequence that is live under a flight has a row in it. A
        sequence whose last token is in flight is left out: its row, like
        a free slot's, stays at position 0, which tells the model that it
        carries no sequence."""
        cfg = self.config
        now = time.monotonic()
        with self._lock:
            live = sorted(self._active.items())
        rows = []
        for slot, seq in live:
            if seq.ends_at(seq.sent, cfg.max_len):
                continue
            if seq.deadline is not None and now > seq.deadline:
                self._evict(seq, "deadline")
                continue
            # the llm.decode fault site is checked once per in-flight
            # sequence so an injected error takes down exactly one of them
            if _faults._ENABLED:
                try:
                    _faults.check(self._FAULT_SITE)
                except Exception as e:
                    self._evict(seq, "error",
                                f"{type(e).__name__}: {e}")
                    continue
            rows.append((slot, seq))
        if not rows:
            return None
        report = lambda: {"kv_pool_bytes": self.kv_pool_bytes()}
        with _monitor.span("llm.decode.dispatch"), \
                _exe.dispatch_guard("llm_decode", report=report):
            s = cfg.num_slots
            pos = np.zeros((s,), np.int32)
            for slot, seq in rows:
                pos[slot] = seq.pos
            if prev is None:
                toks = np.zeros((s,), np.int32)
                for slot, seq in rows:
                    toks[slot] = seq.last_token
            else:
                toks = prev[0]
            outs, donated = self._decode_pool(toks, pos)
        for _, seq in rows:
            seq.pos += 1
        if _monitor._ENABLED:
            _monitor.count("llm.decode.steps")
            if prev is not None:
                _monitor.count("llm.decode.ahead")
            if donated:
                _monitor.count("llm.decode.pool_donated")
            _monitor.count("llm.decode.rows", len(rows))
            _monitor.count("llm.decode.rows_dead", s - len(rows))
            if "state_pool" in self._tags:
                # a step reads and rewrites every state, live or free
                _monitor.count("llm.decode.state_bytes",
                               self.kv_pool_bytes("state_pool"))
            if "kv_pool" in self._tags:
                # rows of a page a step has to read (a row's cached
                # prefix and the token it writes), of the rows held
                _monitor.count("llm.decode.kv_rows_live",
                               int(pos.sum()) + len(rows))
                _monitor.count("llm.decode.kv_rows_pool", s * cfg.max_len)
        return outs[0], rows

    def _collect(self, tokens: Tensor, rows) -> None:
        """Read a dispatched step's tokens, emit them, evict. A row whose
        sequence ended after the dispatch (EOS, a deadline or a fault,
        learnt since) is discarded: its token reaches no stream."""
        cfg = self.config
        # the wait for the device and the d2h copy of the tokens: the one
        # host sync of the decode loop
        with _monitor.span("llm.decode.read"):
            nxt = np.asarray(tokens.numpy())  # tpu-lint: disable=host-sync
        with _monitor.span("llm.emit"):
            now = time.monotonic()
            discarded = 0
            for slot, seq in rows:
                if self._active.get(slot) is not seq:
                    discarded += 1
                    continue
                tok = int(nxt[slot])
                seq.last_token = tok
                seq.stream._emit(tok)
                if _monitor._ENABLED:
                    _monitor.count("llm.tokens_generated")
                    _monitor.observe("llm.inter_token_ms",
                                     (now - seq.last_emit_t) * 1000.0)
                seq.last_emit_t = now
                if tok == cfg.eos_token_id:
                    self._evict(seq, "eos")
                elif seq.ends_at(len(seq.stream.tokens), cfg.max_len):
                    self._evict(seq, "length")
                elif seq.deadline is not None and now > seq.deadline:
                    self._evict(seq, "deadline")
            if _monitor._ENABLED:
                _monitor.count("llm.decode.discarded", discarded)
                _monitor.gauge_set("llm.slots_active", len(self._active))
            self._retag_pool()

    # ---- eviction / bookkeeping --------------------------------------------

    def _evict(self, seq: _Seq, reason: str, error: Optional[str] = None) -> None:
        """Free the sequence's slot and terminate its stream. The pool
        row needs no scrub: free slots are never read (the validity mask
        keys off per-row positions) and the next prefill replaces the
        whole page."""
        with self._lock:
            if self._active.pop(seq.slot, None) is not None:
                self._free.append(seq.slot)
        status = {"eos": "done", "length": "done"}.get(reason, reason)
        self._counters[f"evictions.{reason}"] = \
            self._counters.get(f"evictions.{reason}", 0) + 1
        if _monitor._ENABLED:
            _monitor.count(f"llm.evictions.{reason}")
        self._finish(seq, status, error)

    def _evict_all(self, status: str, error: str) -> None:
        self._flight = None     # its rows' sequences end here, unread
        with self._lock:
            live = list(self._active.values())
            self._active.clear()
            self._free = list(range(self.config.num_slots))
        for seq in live:
            self._counters["evictions.error"] += 1
            if _monitor._ENABLED:
                _monitor.count("llm.evictions.error")
            self._finish(seq, status, error)

    def _finish(self, seq: _Seq, status: str, error: Optional[str]) -> None:
        latency = time.monotonic() - seq.submit_t
        self._counters["completed"] += 1
        if _monitor._ENABLED:
            _monitor.count("llm.completed")
            _monitor.observe("llm.e2e_latency", latency)
        if _slo._ENABLED:
            outcome = {"done": _slo.OUTCOME_OK,
                       "deadline": _slo.OUTCOME_DEADLINE}.get(
                           status, _slo.OUTCOME_ERROR)
            _slo.record_request(
                latency if outcome == _slo.OUTCOME_OK else None, outcome)
        seq.stream._finish(status, error)

    def _retag_pool(self) -> None:
        if _mem._ENABLED:
            for tag in sorted(set(self._tags)):
                _mem.tag(tag, [t._value for t, mine in
                               zip(self._pool, self._tags) if mine == tag],
                         origin="LLMEngine")

    # ---- introspection -----------------------------------------------------

    def kv_pool_bytes(self, tag: Optional[str] = None) -> int:
        """Bytes of the pool, or of its arrays tagged `tag`."""
        total = 0
        for t, mine in zip(self._pool, self._tags):
            if tag is not None and mine != tag:
                continue
            v = t._value
            total += int(getattr(v, "nbytes", 0) or
                         int(np.prod(v.shape)) * v.dtype.itemsize)
        return total

    def stats(self) -> dict:
        with self._lock:
            active, free, queued = (len(self._active), len(self._free),
                                    len(self._pending))
        # a page's positions: absent where the model keeps no page
        pages = [t.shape[1] for t, tag in zip(self._pool, self._tags)
                 if tag == "kv_pool" and len(t.shape) > 1]
        return {
            "slots": self.config.num_slots, "active": active, "free": free,
            "queued": queued, "buckets": list(self.buckets),
            **({"page_len": pages[0]} if pages else {}),
            "kv_pool_bytes": self.kv_pool_bytes(),
            "kv_int8": self.config.kv_int8, "quant": self.config.quant,
            "warm_start_ms": self._warm_ms,
            "counters": dict(self._counters),
        }
