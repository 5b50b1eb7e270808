"""Serving engines: the slot-level paged engine (default) and the
wave-based reference batcher.

decode-time projections are (B x d) @ (d x N) GEMMs with tiny B — the
paper's small-GEMM regime.  Both engines take ONE :class:`repro.api.Policy`
at construction (installed for the whole serving session — not re-entered
per projection); ``Policy(backend="tuned")`` routes those decode GEMMs
and the MoE expert FFN by the measured DeviceProfile.

:class:`PagedEngine` is the production loop for EVERY decoder-only
family: a block/paged KV cache plus per-slot recurrent state
(:mod:`repro.serve.paged`), slot-level admission/eviction/preemption
(:mod:`repro.serve.sched`), chunked prefill interleaved with decode,
sampling fused into the jit'd decode step, and asynchronous token
draining — so the decode batch B stays slot-stable (the Router sees a
stationary shape histogram) and no per-token host sync starves the
tuned kernels.

:class:`ContinuousBatcher` is the wave-based reference implementation:
a wave shares one padded prefill and slots only refill between waves.
It is NOT a production path any more — it survives as the parity
oracle (``slots=1`` is exact unbatched generation, what the paged
differential tests compare against) and for engine-vs-engine
benchmarking in ``benchmarks/serve_stream.py``.

Every request is traced through :mod:`repro.obs`: admission wait, time
to first token, end-to-end latency (all measured from ``submit``),
slot occupancy and preemptions — the numbers the serving-scale ROADMAP
items are judged by (``BENCH_serve.json`` via
``benchmarks/serve_stream.py``).  The paged engine's host loop writes
its own spans (``serve.*``, :class:`repro.obs.span`) into the flight
recorder, on the clock the profiler's device trace uses.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Deque, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro import api, obs
from repro.api import Policy
from repro.models.registry import Model
from repro.serve import sched
from repro.serve.paged import CacheMap, OutOfBlocks, SlotStateStore


def make_serve_fns(model: Model, be: Optional[Policy] = None):
    """Returns (prefill_fn, decode_fn), both jit'd; decode donates cache.
    ``be=None`` snapshots the ambient installed policy once, here — the
    model-entry install point."""
    pol = be if be is not None else api.current_policy()

    def prefill(params, batch):
        return model.prefill(params, batch, pol)

    def decode(params, tokens, cache):
        return model.decode(params, {"tokens": tokens}, cache, pol)

    return (jax.jit(prefill),
            jax.jit(decode, donate_argnums=(2,)))


def sample(logits, key, temperature: float = 0.0,
           vocab: Optional[int] = None):
    """Greedy (temperature 0) or categorical sampling over the last axis;
    ``vocab`` drops the padded tail of a vocab-padded unembedding, so a
    served token is always a real id."""
    if vocab is not None:
        logits = logits[..., :vocab]
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1)
    return jax.random.categorical(key, logits / temperature, axis=-1)


def paged_step_fns(model: Model, be: Policy, temperature: float = 0.0):
    """The paged engine's two device steps, unjitted, so a compile check
    can lower exactly what :class:`PagedEngine` runs:

    ``decode(params, cur, ps, block_tables, pos, active, key)`` ->
    ``(next_tokens (slots,), ps, key)`` — one token for every slot,
    sampled on device;
    ``prefill(params, toks, ps, block_tables, pos0, slot, seg_len,
    n_prompt, last_idx)`` -> ``(logits row (Vp,), ps)`` — one chunk of
    one request."""
    def decode(p, cur, ps, bt, pos, active, k):
        logits, ps = model.paged_decode(
            p, {"tokens": cur[:, None]}, ps, bt, pos, active, be)
        k, sub = jax.random.split(k)
        nxt = sample(logits[:, -1], sub, temperature, model.cfg.vocab)
        return nxt.astype(jnp.int32), ps, k

    def prefill(p, toks, ps, bt, pos0, slot, seg_len, n_prompt, last_idx):
        logits, ps = model.paged_prefill(
            p, {"tokens": toks}, ps, bt, pos0, slot, seg_len, n_prompt, be)
        row = jax.lax.dynamic_index_in_dim(logits[0], last_idx,
                                           axis=0, keepdims=False)
        return row, ps

    return decode, prefill


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray                 # (S,)
    max_new: int = 32
    out: Optional[List[int]] = None
    t_submit: float = 0.0              # perf_counter stamp set by submit()


def _round_up(n: int, m: int) -> int:
    return -(n // -m) * m


# ==========================================================================
# The paged engine (default).
# ==========================================================================

class PagedEngine:
    """Slot-level continuous batching over a paged KV cache.

    Per :meth:`step` iteration: admit queued requests into free slots
    (mid-flight), run ONE jit'd decode step over every decoding slot
    (sampling on device, tokens drained asynchronously every
    ``drain_every`` steps), and run ONE prefill chunk for the oldest
    prefilling request — so a long prompt never stalls ongoing decode.
    Block exhaustion preempts the youngest sequence (blocks AND its
    slot-state row released, generated tokens kept, re-queued at the
    front; resume re-prefills prompt+generated, which rebuilds the
    recurrent carry from zero inside the jit'd prefill step).

    Every lifecycle transition is recorded in the :data:`repro.obs.TRACE`
    flight recorder (REQ_ARRIVE here, ADMIT/RESUME/PREEMPT in the
    scheduler, EVICT in the cache map) so a single request's path
    through the queue/slots is reconstructible after the fact.  The
    loop's host work is recorded there too, as spans whose names
    readers rely on: ``serve.step`` (one iteration that did work) holds
    ``serve.admit``, ``serve.decode_issue`` (info: live slots and their
    context sum), ``serve.prefill_chunk`` (holding ``serve.prefill_sample``
    on a final chunk, info: microseconds of its host sync) and
    ``serve.drain`` (holding one ``serve.drain_wait`` per token array
    pulled to the host); ``serve.preempt`` marks a preemption."""

    def __init__(self, model: Model, params, be: Optional[Policy] = None,
                 *, slots: int = 4, max_len: int = 256, eos: int = 2,
                 temperature: float = 0.0, seed: int = 0,
                 block_size: int = 16, num_blocks: Optional[int] = None,
                 chunk: int = 32, drain_every: int = 4, tuner=None):
        if model.paged_decode is None:
            raise ValueError(
                f"{model.cfg.name}: family {model.cfg.family!r} has no "
                "paged serving path")
        be = be if be is not None else api.current_policy()
        self.model, self.params, self.be = model, params, be
        # optional repro.tune.online.OnlineTuner: run() starts it and
        # stops it on drain, so `--online-tune` serving re-tunes hot
        # classes in the background for exactly the engine's lifetime
        self.tuner = tuner
        self.slots, self.max_len, self.eos = slots, max_len, eos
        self.temperature, self.chunk = temperature, chunk
        self.drain_every = max(1, drain_every)
        self.key = jax.random.PRNGKey(seed)
        # table width covers max_len, rounded so prefill pad rows (the
        # chunk tail past the prompt) always have a backing block
        table_len = _round_up(_round_up(max_len, block_size), chunk)
        if num_blocks is None:
            num_blocks = 1 + slots * (table_len // block_size)
        self.cache = CacheMap(num_blocks, block_size, table_len)
        self.state = SlotStateStore(slots)
        self.scheduler = sched.SlotScheduler(self.cache, slots, self.state)
        self.done: Dict[int, List[int]] = {}
        dtype = model.cfg.compute_dtype
        self._ps = model.init_paged_state(num_blocks, block_size, slots,
                                          dtype)
        self._cur = jnp.zeros((slots,), jnp.int32)
        # (token_array, [(seq, slot)]) per issued decode step, drained
        # in order; holding the arrays (instead of np.asarray per step)
        # is what lets device steps pipeline
        self._pending: List[tuple] = []
        decode, prefill = paged_step_fns(model, be, temperature)
        self._decode_fn = jax.jit(decode, donate_argnums=(2,))
        self._prefill_fn = jax.jit(prefill, donate_argnums=(2,))

    # -- API (mirrors ContinuousBatcher) -----------------------------------

    def submit(self, req: Request) -> None:
        req.t_submit = time.perf_counter()
        if len(req.prompt) + req.max_new > self.max_len:
            raise ValueError(f"request {req.rid} exceeds max_len "
                             f"{self.max_len}")
        obs.counter("serve.requests").inc()
        obs.TRACE.emit("REQ_ARRIVE", rid=req.rid,
                       arg=(len(req.prompt), req.max_new))
        seq = sched.Seq(req=req)
        # worst-case footprint: the longest possible resume target
        # (prompt + max_new-1 generated) prefilled with a chunk-padded
        # tail — what the fit check must clear for livelock-free preempt
        worst = _round_up(
            max(1, len(req.prompt) + req.max_new - 1), self.chunk)
        self.scheduler.submit(seq, fit_tokens=worst)

    def step(self) -> bool:
        """One scheduler iteration; False when fully idle.  An idle call
        returns before it opens a span, so an idle loop records nothing:
        with a request queued, in a slot or a token array pending, the
        iteration admits, decodes, prefills or drains."""
        if not (self.scheduler.has_work() or self._pending):
            return False
        with obs.span("serve.step"):
            worked = False
            if self.scheduler.queue and self.scheduler.active() < self.slots:
                with obs.span("serve.admit"):
                    now = time.perf_counter()
                    for seq in self.scheduler.admit():
                        worked = True
                        if not seq.admitted_once:
                            seq.admitted_once = True
                            obs.histogram("serve.admission_wait_us").record(
                                (now - seq.req.t_submit) * 1e6)
            dec = [q for q in self.scheduler.decoding() if q.budget_left > 0]
            for q in list(dec):
                if q.state == sched.DECODE:
                    self._ensure(q, q.pos + 1)
            dec = [q for q in self.scheduler.decoding() if q.budget_left > 0]
            if dec:
                self._issue_decode(dec)
                worked = True
            pre = self.scheduler.next_prefill()
            if pre is not None:
                self._prefill_chunk(pre)
                worked = True
            if self._pending and (
                    len(self._pending) >= self.drain_every
                    or not any(q.budget_left > 0
                               for q in self.scheduler.decoding())):
                self._drain()
            if worked:
                obs.histogram("serve.slot_occupancy").record(
                    self.scheduler.active() / self.slots)
        return worked

    def run(self) -> Dict[int, List[int]]:
        if self.tuner is not None:
            self.tuner.start()      # no-op under REPRO_ONLINE_TUNE=0
        try:
            stall = 0
            while True:
                if self.step():
                    stall = 0
                    continue
                if self._pending:
                    self._drain()
                    continue
                if not self.scheduler.has_work():
                    break
                stall += 1
                if stall > 10000:   # fail loudly, never hang
                    raise RuntimeError("paged engine stalled: "
                                       f"{self.scheduler.active()} live, "
                                       f"{len(self.scheduler.queue)} queued")
        finally:
            # clean shutdown on drain (or on a raise): the tuner thread
            # joins before run() returns, so no background timing work
            # outlives the engine loop
            if self.tuner is not None:
                self.tuner.stop()
        return self.done

    # -- internals ---------------------------------------------------------

    def _ensure(self, seq: sched.Seq, n_tokens: int) -> bool:
        """Back ``seq`` with blocks for ``n_tokens`` positions,
        preempting (youngest first) on exhaustion.  False when ``seq``
        itself was the victim (it is re-queued; stop working on it)."""
        drained = False
        while True:
            try:
                self.cache.ensure(seq.rid, n_tokens)
                return True
            except OutOfBlocks:
                if not drained and self._pending:
                    self._drain()      # EOS finishes may free blocks
                    drained = True
                    if seq.state != sched.DECODE and \
                            seq.state != sched.PREFILL:
                        return False   # finished during the drain
                    continue
                self._drain()
                victim = self.scheduler.preempt_victim(seq)
                if victim is None:
                    raise RuntimeError("block pool exhausted with no "
                                       "active sequence to preempt")
                if victim is seq and self.scheduler.active() == 1:
                    raise RuntimeError(
                        "block pool exhausted by a single sequence that "
                        "passed the admission fit check — pool leak?")
                with obs.span("serve.preempt", info=victim.rid):
                    self.scheduler.preempt(victim)
                if victim is seq:
                    return False

    def _issue_decode(self, dec: List[sched.Seq]) -> None:
        with obs.span("serve.decode_issue") as sp:
            bt = np.zeros((self.slots, self.cache.nmax), np.int32)
            pos = np.zeros((self.slots,), np.int32)
            act = np.zeros((self.slots,), bool)
            ctx = 0
            for q in dec:
                bt[q.slot] = self.cache.row(q.rid)
                pos[q.slot] = q.pos
                act[q.slot] = True
                ctx += q.pos + 1
            sp.info = (len(dec), ctx)
            self._cur, self._ps, self.key = self._decode_fn(
                self.params, self._cur, self._ps,
                jnp.asarray(bt), jnp.asarray(pos), jnp.asarray(act),
                self.key)
            self._pending.append((self._cur, [(q, q.slot) for q in dec]))
            for q in dec:
                q.pos += 1
                q.inflight += 1

    def _prefill_chunk(self, seq: sched.Seq) -> None:
        with obs.span("serve.prefill_chunk"):
            p0, C = seq.pos, self.chunk
            if not self._ensure(seq, p0 + C):
                return                  # preempted itself; re-queued
            target = seq.target
            segment = target[p0:p0 + C]
            toks = np.zeros((1, C), np.int32)
            toks[0, :len(segment)] = segment
            final = (p0 + len(segment)) == len(target)
            last_idx = np.int32(len(segment) - 1)
            t_chunk = time.perf_counter()
            row, self._ps = self._prefill_fn(
                self.params, jnp.asarray(toks), self._ps,
                jnp.asarray(self.cache.row(seq.rid)[None]),
                jnp.asarray([p0], dtype=jnp.int32), np.int32(seq.slot),
                np.int32(len(segment)), np.int32(len(seq.req.prompt)),
                last_idx)
            seq.pos = p0 + len(segment)
            obs.counter("serve.prefill_chunks").inc()
            obs.TRACE.emit(
                "PREFILL_CHUNK", rid=seq.rid, slot=seq.slot,
                arg=(p0, len(segment)),
                dur_us=(time.perf_counter() - t_chunk) * 1e6)
            if final:
                self._sample_boundary(seq, row)

    def _sample_boundary(self, seq: sched.Seq, row) -> None:
        """Host-side sample of the prefill boundary token only — every
        later token is sampled inside the jit'd decode step.  The
        ``serve.prefill_sample`` span covers the sample up to its host
        sync and the ``_cur`` update; its info is the sync's
        microseconds."""
        with obs.span("serve.prefill_sample") as sp:
            self.key, k = jax.random.split(self.key)
            tok = sample(row, k, self.temperature, self.model.cfg.vocab)
            t_sync = time.perf_counter()
            tok = int(np.asarray(tok))
            sp.info = (time.perf_counter() - t_sync) * 1e6
            seq.out.append(tok)
            # like the wave reference, the request's FIRST token is
            # exempt from EOS (a request always yields at least one
            # token); a post-preemption boundary token is an ordinary
            # decode token and does get the EOS check
            done = (tok == self.eos and len(seq.out) > 1) \
                or len(seq.out) >= seq.req.max_new
            if not done:
                self._cur = self._cur.at[seq.slot].set(tok)
        obs.counter("serve.tokens").inc()
        if len(seq.out) == 1:
            obs.histogram("serve.ttft_us").record(
                (time.perf_counter() - seq.req.t_submit) * 1e6)
            obs.TRACE.emit("FIRST_TOKEN", rid=seq.rid, slot=seq.slot)
        if done:
            self._finish(seq)
        else:
            seq.state = sched.DECODE

    def _drain(self) -> None:
        """Pull every pending decode token to the host in one pass and
        apply EOS / token-budget eviction with the (bounded) lag the
        async pipeline allows.  ``serve.drain`` holds one
        ``serve.drain_wait`` per token array: the host sync alone."""
        with obs.span("serve.drain"):
            pend, self._pending = self._pending, []
            for arr, entries in pend:
                with obs.span("serve.drain_wait"):
                    host = np.asarray(arr)
                for q, slot in entries:
                    q.inflight -= 1
                    if q.state != sched.DECODE:
                        continue        # evicted earlier in this drain
                    tok = int(host[slot])
                    q.out.append(tok)
                    obs.counter("serve.tokens").inc()
                    if tok == self.eos or len(q.out) >= q.req.max_new:
                        self._finish(q)

    def _finish(self, seq: sched.Seq) -> None:
        self.done[seq.rid] = seq.out
        obs.histogram("serve.e2e_us").record(
            (time.perf_counter() - seq.req.t_submit) * 1e6)
        obs.TRACE.emit("FINISH", rid=seq.rid, slot=seq.slot,
                       arg=len(seq.out))
        self.scheduler.finish(seq)


# ==========================================================================
# The wave-based reference engine.
# ==========================================================================

class ContinuousBatcher:
    """Wave-based continuous batching over a fixed decode batch.

    Simplification vs the paged engine: prompts in one admission wave
    share a prefill call (padded to the longest), ``cache_len`` is
    pre-committed for the whole wave, and slots only refill between
    waves.  Retired from production serving (the launcher only builds
    :class:`PagedEngine` now); kept as the parity ORACLE — ``slots=1``
    is exact unbatched generation, the baseline the paged differential
    suite compares every family against — and for the engine-vs-engine
    benchmark in ``benchmarks/serve_stream.py``."""

    def __init__(self, model: Model, params, be: Optional[Policy] = None,
                 *, slots: int = 4, max_len: int = 256, eos: int = 2,
                 temperature: float = 0.0, seed: int = 0):
        # the policy is resolved ONCE at engine construction (model
        # entry); every projection below reads this frozen object.
        be = be if be is not None else api.current_policy()
        self.model, self.params, self.be = model, params, be
        self.slots, self.max_len, self.eos = slots, max_len, eos
        self.temperature = temperature
        self.key = jax.random.PRNGKey(seed)
        self.queue: Deque[Request] = collections.deque()
        self.done: Dict[int, List[int]] = {}

        def _decode(p, t, c, k):
            logits, c = model.decode(p, {"tokens": t}, c, be)
            # sampling fused into the step: only (B,) token ids cross
            # to the host, never the (B, V) logits
            return sample(logits, k, temperature,
                          model.cfg.vocab).astype(jnp.int32), c

        self._decode = jax.jit(_decode)

    def submit(self, req: Request) -> None:
        req.t_submit = time.perf_counter()
        obs.counter("serve.requests").inc()
        self.queue.append(req)

    def step(self) -> bool:
        """Admit and run ONE wave from the queue; False when idle.  The
        streaming benchmark drives this directly so new arrivals can be
        submitted between waves (Poisson arrivals against a wave-based
        scheduler — the admission-wait histogram prices that gap)."""
        if not self.queue:
            return False
        wave = [self.queue.popleft() for _ in range(
            min(self.slots, len(self.queue)))]
        self._run_wave(wave)
        return True

    def run(self) -> Dict[int, List[int]]:
        while self.step():
            pass
        return self.done

    def _run_wave(self, wave: List[Request]) -> None:
        B = len(wave)
        t_admit = time.perf_counter()
        adm = obs.histogram("serve.admission_wait_us")
        for r in wave:
            adm.record((t_admit - r.t_submit) * 1e6)
        obs.histogram("serve.wave_occupancy").record(B / self.slots)
        S = max(len(r.prompt) for r in wave)
        toks = np.zeros((B, S), np.int32)
        for i, r in enumerate(wave):
            toks[i, S - len(r.prompt):] = r.prompt     # left-pad
        max_new = max(r.max_new for r in wave)
        with obs.span("serve.prefill"):
            logits, cache = self.model.prefill(
                self.params, {"tokens": jnp.asarray(toks)}, self.be,
                cache_len=min(S + max_new, self.max_len))
            logits = jax.block_until_ready(logits)
        outs = [[] for _ in wave]
        alive = np.ones(B, bool)
        cur = np.asarray(sample(logits, self.key, self.temperature,
                                self.model.cfg.vocab))
        t_first = time.perf_counter()
        ttft = obs.histogram("serve.ttft_us")
        for i in range(B):
            outs[i].append(int(cur[i]))
            ttft.record((t_first - wave[i].t_submit) * 1e6)
        steps = max(r.max_new for r in wave) - 1
        decoded = 0
        cur_dev = jnp.asarray(cur.astype(np.int32))
        with obs.span("serve.decode"):
            for _ in range(max(steps, 0)):
                if not alive.any():
                    break
                self.key, k = jax.random.split(self.key)
                cur_dev, cache = self._decode(
                    self.params, cur_dev[:, None], cache, k)
                cur = np.asarray(cur_dev)
                for i in range(B):
                    if alive[i]:
                        tok = int(cur[i])
                        outs[i].append(tok)
                        decoded += 1
                        if tok == self.eos or \
                                len(outs[i]) >= wave[i].max_new:
                            alive[i] = False
        t_done = time.perf_counter()
        if decoded and t_done > t_first:
            obs.histogram("serve.decode_tok_s").record(
                decoded / (t_done - t_first))
        e2e = obs.histogram("serve.e2e_us")
        toks_out = obs.counter("serve.tokens")
        for r, o in zip(wave, outs):
            self.done[r.rid] = o
            e2e.record((t_done - r.t_submit) * 1e6)
            toks_out.inc(len(o))
