"""Model runners: where the serving engine crosses into "device" execution.

This boundary is the JAX analogue of vLLM/SGLang's CUDA call sites, and the
*only* place Revati integration touches the engine (the paper's "<25 lines to
onboard a serving system" — here it is the :class:`TimeWarpModelRunner`):

* :class:`RealModelRunner` — executes the actual JAX model (ground truth for
  the fidelity benchmarks; a reduced model on the CPU in tests, published
  widths on a TPU).  Also doubles as the profiler whose step samples fit a
  predictor.
* :class:`TimeWarpModelRunner` — Revati: predicts the step duration and
  requests a TIMEJUMP instead of executing.  Weights and KV pool are
  ComputeBuffers in the VirtualDeviceContext (split-state memory model);
  returned token values are constants — a successful run proves the control
  plane never consumed phantom data.
* :class:`SleepModelRunner` — the paper's strawman: predict, then *sleep* the
  wall clock for the duration (correct but slow; Figs. 8–10 baseline).

All runners share the BatchSpec translation, so predictor inputs are
identical across modes by construction.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from repro.core.client import TimeJumpClient
from repro.core.clock import VirtualClock
from repro.core.emulation import VirtualDeviceContext
from repro.core.predictor import BatchSpec, RuntimePredictor, SeqSpec
from repro.core.spans import span

from .scheduler import ScheduledSeq, SchedulerOutput

DUMMY_TOKEN = 0  # emulated modes: values are never consumed by control flow


def batch_spec_of(out: SchedulerOutput) -> BatchSpec:
    seqs = []
    for s in out.batch:
        req = s.request
        seqs.append(SeqSpec(
            new_tokens=s.num_new_tokens,
            context_len=req.context_len + s.num_new_tokens,
            cached_prefix=req.cached_prefix_len if s.is_prefill else 0,
        ))
    return BatchSpec.make(tuple(seqs))


def _producing(out: SchedulerOutput) -> List[ScheduledSeq]:
    """Sequences that emit a token this step (decode + final prefill chunk)."""
    res = []
    for s in out.batch:
        req = s.request
        if not s.is_prefill:
            res.append(s)
        elif req.num_prefilled + s.num_new_tokens >= req.prompt_len:
            res.append(s)
    return res


class TimeWarpModelRunner:
    """Revati's device-side integration: ~20 effective lines of engine patch.

    Each ``execute`` asks the predictor "how long would this batch take on
    the target hardware?" and jumps virtual time by the answer through the
    Timekeeper.  With ``workers`` set, the jump is performed by every worker
    of the TP group plus a collective barrier (NCCL-as-barrier, §4.3).
    """

    def __init__(
        self,
        predictor: RuntimePredictor,
        client: Optional[TimeJumpClient] = None,
        *,
        workers: Optional["object"] = None,   # repro.serving.workers.WorkerGroup
        devices: Optional[VirtualDeviceContext] = None,
        weight_bytes: int = 0,
        kv_pool_bytes: int = 0,
    ):
        self.predictor = predictor
        self.client = client
        self.workers = workers
        self.devices = devices
        # per-step breakdown for the accuracy/split figures; audit modes
        # below "full" switch retention off so memory stays flat over
        # million-request streams (engine.set_audit flips the flag)
        self.retain_estimates = True
        self.step_estimates: List[dict] = []
        if devices is not None:
            n = len(devices.devices)
            self._buffers = []
            for d in range(n):
                if weight_bytes:
                    self._buffers.append(devices.malloc(
                        weight_bytes // n, d, tag="weights"))
                if kv_pool_bytes:
                    self._buffers.append(devices.malloc(
                        kv_pool_bytes // n, d, tag="kv_pool"))

    # ------------------------------------------------------------ running --
    def execute(self, out: SchedulerOutput) -> Dict[int, int]:
        est = self.predictor.predict_step(batch_spec_of(out))
        if self.retain_estimates:
            self.step_estimates.append(est.as_dict())
        if self.workers is not None:
            self.workers.execute_step(est.total)
        elif self.client is not None:
            self.client.time_jump(est.total)          # <-- the Revati patch
        return {s.request.request_id: DUMMY_TOKEN for s in _producing(out)}

    # actor lifecycle (engine parks when idle so it never wedges the barrier)
    def park(self) -> None:
        if self.workers is not None:
            self.workers.park()
        elif self.client is not None:
            self.client.park()

    def unpark(self) -> None:
        if self.workers is not None:
            self.workers.unpark()
        elif self.client is not None:
            self.client.unpark()

    def retire(self) -> None:
        """Permanent departure from the Timekeeper (cluster drain): a real
        deregistration — with the barrier re-evaluation + epoch bump that
        park lacks — so a drained replica is forgotten entirely."""
        if self.workers is not None:
            self.workers.park()          # WorkerGroup park == deregister all
        elif self.client is not None:
            self.client.deregister()

    def shutdown(self) -> None:
        self.park()
        if self.workers is not None:
            self.workers.shutdown()


class SleepModelRunner:
    """Strawman sleep-based emulation (§3.2): correct, wall-clock slow."""

    def __init__(self, predictor: RuntimePredictor, clock: VirtualClock):
        self.predictor = predictor
        self.clock = clock
        self.retain_estimates = True
        self.step_estimates: List[dict] = []

    def execute(self, out: SchedulerOutput) -> Dict[int, int]:
        est = self.predictor.predict_step(batch_spec_of(out))
        if self.retain_estimates:
            self.step_estimates.append(est.as_dict())
        # Precise (spin-tailed) sleep: plain time.sleep overshoots by OS timer
        # slop, which would systematically bias this baseline slow.
        self.clock.wall.sleep_precise(est.total)
        return {s.request.request_id: DUMMY_TOKEN for s in _producing(out)}

    def park(self) -> None: ...
    def unpark(self) -> None: ...
    def retire(self) -> None: ...
    def shutdown(self) -> None: ...


def _slot_axis(key: str, uniform) -> int:
    """Batch axis of a cache entry: stacked layers lead with the layer axis,
    as do the enc-dec cross-attention K/V."""
    if key in ("cross_k", "cross_v"):
        return 1
    if key == "layers" and uniform is not None:
        return 1
    return 0


def _take_slot(uniform, cache, slot):
    import jax
    return {key: jax.tree.map(
        lambda x, a=_slot_axis(key, uniform):
            jax.lax.dynamic_slice_in_dim(x, slot, 1, a), sub)
        for key, sub in cache.items()}


def _put_slot(uniform, cache, small, slot):
    import jax
    return {key: jax.tree.map(
        lambda big, x, a=_slot_axis(key, uniform):
            jax.lax.dynamic_update_slice_in_dim(
                big, x.astype(big.dtype), slot, a), sub, small[key])
        for key, sub in cache.items()}


def _prefill_slot(model, params, cache, slot, tokens, positions):
    """One prompt chunk of the sequence in ``slot``: read its cache rows,
    extend them, write them back (the shared cache is donated, so XLA
    updates it in place)."""
    uniform = getattr(model, "uniform", "x")
    small = _take_slot(uniform, cache, slot)
    logits, small = model.prefill(
        params, {"tokens": tokens, "positions": positions}, small)
    return logits, _put_slot(uniform, cache, small, slot)


class RealModelRunner:
    """Executes the actual JAX model — ground truth for fidelity runs.

    Slot-based execution with fixed shapes (no recompilation in steady
    state): a shared cache holds ``max_seqs`` slots of ``max_len``
    positions plus a scratch region; prefill chunks run per sequence
    (batch 1, bucketed chunk lengths) inside one jitted program that reads
    and writes the sequence's slot in place.  Mixed batches execute as
    prefill calls + one batched decode call; the wall-clock sum, taken once
    the device has finished everything the step produced, is the step's
    real duration (recorded for predictor calibration).  The cache takes
    the weights' dtype.

    ``execute`` runs in spans (``revati.runner.execute`` around
    ``.prefill`` per prompt chunk, ``.feed``, ``.dispatch``, ``.sample``,
    ``.wait``, ``.release``) and leaves ``last_phases``: its wall seconds
    outside ``.wait`` and inside it, the host blocked on the device.  The
    jitted programs are named ``prefill_chunk``, ``reset_slot``,
    ``decode_step`` and ``sample`` in a profile.
    """

    def __init__(self, model, params, *, max_seqs: int, max_len: int,
                 clock: VirtualClock, chunk_buckets=(32, 64, 128, 256, 512)):
        import jax
        import jax.numpy as jnp

        self.model = model
        self.params = params
        self.max_seqs = max_seqs
        self.max_len = max_len
        self.clock = clock
        self.chunk_buckets = tuple(sorted(chunk_buckets))
        self.dtype = params["embed"].dtype
        # Padded prefill is only sound for pure-attention stacks (pad KV is
        # position-masked).  Recurrent blocks (SSD / RG-LRU) would fold pad
        # tokens into their state, so those archs run exact-length chunks
        # (one extra compile per distinct remainder length).
        kinds = set(getattr(model.cfg, "layer_pattern", ("attn",)))
        self._pad_prefill = kinds <= {"attn", "local_attn"}
        self._jax = jax
        self._jnp = jnp
        # Positions from max_len on are scratch: pad tokens and the decode
        # rows of idle slots are written there, past every real position,
        # so causal masking hides them from every real query.
        slack = self.chunk_buckets[-1]
        self._empty = model.init_cache(1, max_len, self.dtype,
                                       window_slack=slack)
        self.cache = model.init_cache(max_seqs, max_len, self.dtype,
                                      window_slack=slack)
        self._slot_of: Dict[int, int] = {}
        self._free_slots = list(range(max_seqs))[::-1]
        uniform = getattr(model, "uniform", "x")

        def prefill_chunk(params, cache, slot, tokens, positions):
            return _prefill_slot(model, params, cache, slot, tokens,
                                 positions)

        def reset_slot(cache, small, slot):
            return _put_slot(uniform, cache, small, slot)

        def sample(logits):
            return jnp.argmax(logits, axis=-1)

        self._decode = jax.jit(model.decode_step, donate_argnums=(1,))
        self._prefill = jax.jit(prefill_chunk, donate_argnums=(1,))
        self._reset = jax.jit(reset_slot, donate_argnums=(0,))
        self._sample = jax.jit(sample)
        self.samples: List[tuple] = []       # (BatchSpec, seconds) for fitting
        self.last_phases = (0.0, 0.0)        # (host s, wait s) of the last step

    # ------------------------------------------------------------ warmup --
    def warmup(self) -> None:
        """Compile every steady-state program (prefill buckets, slot reset,
        the batched decode, sampling) outside measured time.  Without this,
        first-call XLA compiles (seconds) land inside step timings and
        poison both the predictor calibration and the fidelity comparison —
        the real-hardware analogue of excluding warmup iterations from
        profiling.  Everything it writes lands in the scratch region."""
        cfg = self.model.cfg
        slot = np.int32(0)
        self.cache = self._reset(self.cache, self._empty, slot)
        if self._pad_prefill and cfg.frontend is None:
            for b in self.chunk_buckets:
                toks = np.zeros((1, b), np.int32)
                pos = (self.max_len + np.arange(b, dtype=np.int32))[None]
                logits, self.cache = self._prefill(
                    self.params, self.cache, slot, toks, pos)
                self._sample(logits)
        logits = self.decode({})
        self._jax.block_until_ready((self._sample(logits), self.cache))

    # ------------------------------------------------------ device steps --
    def acquire(self, request_id: int) -> int:
        """Give ``request_id`` a slot, cleared of any earlier sequence."""
        slot = self._free_slots.pop()
        self._slot_of[request_id] = slot
        self.cache = self._reset(self.cache, self._empty, np.int32(slot))
        return slot

    def prefill_chunk(self, slot: int, chunk: List[int], start: int):
        """Extend the sequence in ``slot`` by ``chunk`` at positions
        ``start``...; returns the logits (1, V) of the chunk's last token.

        The chunk is left-padded up to its bucket: pad tokens come first, at
        scratch positions, so the model's last row is the last real token
        and a final chunk yields the request's true first token."""
        if self._pad_prefill:
            bucket = next((b for b in self.chunk_buckets if b >= len(chunk)),
                          len(chunk))
        else:
            bucket = len(chunk)
        pad = bucket - len(chunk)
        toks = np.asarray([0] * pad + list(chunk), np.int32)[None]
        positions = np.concatenate([
            self.max_len + np.arange(pad),
            start + np.arange(len(chunk))]).astype(np.int32)[None]
        logits, self.cache = self._prefill(
            self.params, self.cache, np.int32(slot), toks, positions)
        return logits

    def decode(self, feeds: Dict[int, tuple]):
        """One batched decode over every slot.  ``feeds`` maps slot ->
        (token, position); idle slots decode a throwaway token into the
        scratch region.  Returns logits (max_seqs, V)."""
        with span("revati.runner.feed"):
            tokens = np.zeros((self.max_seqs, 1), np.int32)
            positions = np.full((self.max_seqs,), self.max_len, np.int32)
            for slot, (tok, pos) in feeds.items():
                tokens[slot, 0] = tok
                positions[slot] = pos
            self.cache["cache_len"] = self._jnp.asarray(positions)
        with span("revati.runner.dispatch"):
            logits, self.cache = self._decode(self.params, self.cache, tokens)
        return logits

    # ------------------------------------------------------------ running --
    def execute(self, out: SchedulerOutput) -> Dict[int, int]:
        t0 = time.monotonic()
        with span("revati.runner.execute"):
            firsts = []                      # (request_id, device argmax)
            for s in out.batch:
                if not s.is_prefill:
                    continue
                with span("revati.runner.prefill"):
                    req = s.request
                    slot = self._slot_of.get(req.request_id)
                    if slot is None:
                        slot = self.acquire(req.request_id)
                    start = req.num_prefilled
                    chunk = req.prompt_tokens[start : start + s.num_new_tokens]
                    logits = self.prefill_chunk(slot, chunk, start)
                    if start + len(chunk) >= req.prompt_len:
                        firsts.append((req.request_id, self._sample(logits)))

            # The newest token is counted in context_len but not yet cached:
            # it is fed at position context_len - 1.
            decodes = [s.request for s in out.batch if not s.is_prefill]
            feeds = {self._slot_of[r.request_id]:
                     (r.output_tokens[-1], r.context_len - 1) for r in decodes}
            picked = ()
            if feeds:
                logits = self.decode(feeds)
                with span("revati.runner.sample"):
                    picked = self._sample(logits)

            t_wait = time.monotonic()
            with span("revati.runner.wait"):
                picked = np.asarray(picked)
                firsts = [(rid, int(np.asarray(t)[0])) for rid, t in firsts]
                self._jax.block_until_ready(self.cache)
            t_ready = time.monotonic()
            tokens = dict(firsts)
            for r in decodes:
                tokens[r.request_id] = int(picked[self._slot_of[r.request_id]])
            self.samples.append((batch_spec_of(out), t_ready - t0))

            # release slots of finishing requests
            with span("revati.runner.release"):
                for s in out.batch:
                    req = s.request
                    if (not s.is_prefill and
                            req.num_generated + 1 >= req.max_new_tokens):
                        self.release(req.request_id)
        wait = t_ready - t_wait
        self.last_phases = (time.monotonic() - t0 - wait, wait)
        return tokens

    def release(self, request_id: int) -> None:
        slot = self._slot_of.pop(request_id, None)
        if slot is not None:
            self._free_slots.append(slot)

    def park(self) -> None: ...
    def unpark(self) -> None: ...
    def shutdown(self) -> None: ...
