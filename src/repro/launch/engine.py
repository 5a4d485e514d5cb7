"""Continuous-batching serving engine with a unified request-level API.

Everything the launch layer serves — the one-shot ``serve`` CLI, the plan
runner, the serving benchmark, and the tests — builds its model/mesh/param
stack through one entry point, ``EngineConfig.build()``, and talks to the
model at request granularity through ``EpimEngine``.

API reference
-------------
``Request``
    Frozen per-request spec: ``prompt`` (token ids), ``max_new_tokens``,
    ``temperature`` (0 = greedy), ``seed``.  The seed is the *request's*
    sampling identity: the engine folds ``jax.random.PRNGKey(seed)`` into
    the slot the request lands in, so the sampled continuation depends
    only on the request — never on arrival order or batch position.

``Completion``
    Frozen result: ``request_id``, ``prompt_len``, ``tokens`` (the
    generated ids, prompt excluded), ``ttft_s`` (submit -> first token),
    ``latency_s`` (submit -> last token), ``queue_wait_s`` (submit ->
    admission: how long the request sat behind slot/page scarcity), and
    ``token_times`` (a perf_counter stamp per emitted token — the
    serving benchmark derives inter-token decode gaps from these).

``RequestHandle``
    Returned by ``submit``; ``done()`` / ``result()`` poll the completion;
    ``token_times`` gives the per-token stamps so far, finished or not.

``EngineConfig``
    Dataclass of everything needed to stand a server up: ``arch``,
    ``epitome``, ``plan`` (path or EpitomePlan), ``mesh`` ('' = data
    parallel over all devices, 'DATA,MODEL' = explicit sharded mesh,
    ``None`` = leave the global mesh untouched), ``smoke``, ``prepack``,
    ``capacity`` (decode slots), ``max_len`` (per-request token budget),
    ``page_size`` / ``kv_pages`` (block-paged KV pool geometry; 0 page
    size = dense per-slot blocks), ``prefill_chunk`` (chunked-prefill
    granularity; 0 = whole-prompt prefill), ``seed`` (param init).
    ``build()`` performs the whole setup that serve.py/plan.py used to
    duplicate — config resolution, param init, weight-stationary int8
    prepack, mesh layout — and returns a ready ``EpimEngine`` (with
    ``.cfg/.params/.packed/.serve_params/.mesh/.prompt_key/.sample_key``
    exposed for one-shot callers).

``EpimEngine``
    ``submit(request) -> RequestHandle`` validates the request (length
    vs ``max_len``, token ids vs the vocab, page feasibility) and admits
    it when a slot AND its KV pages are free; ``step()`` runs at most one
    prefill chunk plus ONE batched decode step over every active slot
    and returns how many tokens were emitted; ``drain()`` steps until
    idle and returns every completion in submission order.  ``stats``
    counts ``prefill_traces`` / ``prefill_chunks`` / ``slot_reuses`` /
    ``decode_steps`` / ``decode_micro_steps`` / ``decode_traces`` /
    ``completed`` / ``admitted``, ``prefill_s``: host seconds from
    each prefill call until its first token is on the host (see
    Tracing), and ``expert_rows``: the (token, held expert) pairs the MoE
    layers routed in decode dispatches (counted on the device, read with
    the tokens); plus occupancy: ``queue_depth`` and the pool's
    ``pages_total`` / ``pages_used`` / ``pages_free`` / ``pages_hwm`` /
    ``page_reuses``.

Scheduling model
----------------
The engine owns ONE pooled decode-state abstraction
(``models/kv_pool.SlotStatePool``) whose batch axis is ``capacity``
request slots — dense recurrent rows per slot for the SSM/RWKV blocks,
and a *block-paged* KV pool for attention: a global pool of
``kv_pages`` fixed-size pages (``page_size`` tokens each) plus a
per-slot page table the jitted decode gathers K/V through.  Admission
reserves every page the request will ever need
(ceil((P + max_new_tokens) / page_size)) so decode can never starve
mid-flight; when the pool is dry the queue head *defers* (FIFO
head-of-line) until a completion frees pages.  Sizing ``kv_pages``
below ``capacity * pages_per_slot`` oversubscribes the pool — more
tokens of capacity per byte, the same move the paper makes for weights.
A free-list hands slots out; a finished request frees its slot and
pages mid-flight and the next pending request scatters a fresh prefill
state over it (``SlotStatePool.scatter``).  Decode runs at the full
pool width with per-slot positions (``pos (C,)``) — freed/idle slots
compute garbage in their own rows (and write it to the pool's trash
page), which per-row independence and the attention-side masking keep
away from live requests.

Chunked prefill
---------------
Prompts longer than ``prefill_chunk`` no longer prefill whole inside
``step()``: the engine runs ONE chunk per step (first chunk at
admission), interleaved with the batched decode tick, so a long-prompt
arrival bounds its decode stall at one chunk instead of one prompt.
The chunk length is rounded up to ``models/ssm.recurrence_alignment``
(the lcm of the rwkv/mamba internal scan windows present) so chunk
boundaries coincide with the windows the one-shot prefill already uses
internally — that alignment is what keeps chunked recurrences
bit-identical.  The transient chunk state carries attention K/V in
float32 so later chunks attend earlier chunks' K/V at exactly the
precision the one-shot path attends them fresh (the final scatter into
the pool rounds to cache dtype, exactly where the one-shot path
rounds).  Short prompts (P <= chunk) keep the immediate bucketed
one-shot prefill.  Exceptions that prefill whole-prompt: MoE layers
that a mesh would run as a capacity dispatch (it couples every token in
the dispatch; the per-token MoE path of one device is row-local and
chunks like any layer) and int8 KV caches (chunk 2 would attend
dequantized rows where one-shot attends fresh float K/V).

Prompt bucketing
----------------
One-shot prefills pad prompts up to power-of-two buckets (min 8,
capped at the pool sequence length) so distinct prompt lengths reuse
one compiled program per bucket; chunked prefills compile ONE program
per (cfg, chunk) regardless of prompt length.  Pads sit strictly AFTER
the real tokens and every mixer masks them to exact zeros / exact
identities (``valid_len`` threading in models/*).  MoE layers that a
mesh would run as a capacity dispatch prefill at exact length (one trace
per distinct length).

Bit-exactness contract
----------------------
For any single request the engine's output is bit-identical to the
pre-existing one-shot path (``serve.generate`` with the same ``max_len``
and ``key=jax.random.PRNGKey(request.seed)``), greedy and sampled,
single-device and sharded — with paging on or off, chunked or whole
prefill: right-padded masked prefill keeps real-token bits; chunk
boundaries sit on recurrence-window boundaries; paged attention gathers
the same rows dense attention reads in place; decode rows are
independent so batch width doesn't perturb a request; and
``jax.random.categorical`` over a ``(V,)`` row draws the same bits as
over ``(1, V)`` (flat threefry counter reshape).

Tracing
-------
The engine marks its layer boundaries with ``jax.profiler`` host spans,
which land in the same profile as the device's ops, on one clock; they
cost a few objects per step when no profile is recording.  ``epim.step``
(a step annotation numbered by ``decode_steps``) wraps ``step()``;
``epim.submit`` wraps ``submit()``; ``epim.admit`` (``rid``, ``slot``)
an admission; ``epim.prefill`` (``rid`` and ``bucket``, or ``chunk``) a
prefill call up to its first token on the host, with two children: the
launches that activate it (``epim.activate``: the scatter into the pool
and the carry poke) and the blocking read of that token
(``epim.prefill.wait``); ``epim.retire`` the
bookkeeping of a finished dispatch, with ``epim.retire.wait`` around the
blocking read of its tokens; ``epim.dispatch`` (``k``, ``live`` slots)
the launch of the next one.  ``stats["prefill_s"]`` adds up the host
time of the ``epim.prefill`` spans, traced or not; an intermediate chunk
of a chunked prefill counts only its launch, since the engine never
waits on it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import itertools
import time
from collections import deque
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, get_smoke_config
from ..models import lm, moe
from ..models.common import set_mesh
from ..models.kv_pool import SlotStatePool, paged_leaf_paths
from ..models.ssm import recurrence_alignment
from .mesh import make_host_mesh, mesh_for_plan, parse_mesh

# Python-side counter bumped inside the jitted prefill bodies: it only
# fires when XLA (re)traces, so deltas count compiled prefill programs.
# Engines attribute the deltas around their OWN prefill calls to a
# per-engine counter (stats["prefill_traces"]), so two engines in one
# process do not corrupt each other's numbers.
PREFILL_TRACES = [0]

# Same idea for the fused decode macro-step (stats["decode_traces"]): one
# compiled program per (cfg, K) — deltas bound how many K values the
# auto-pick rule visited, NOT how many requests were served.
DECODE_TRACES = [0]


# ---------------------------------------------------------------------------
# Request-level API
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request.  ``prompt`` is coerced to a tuple of ints so
    requests are hashable/immutable; ``temperature`` 0 means greedy."""
    prompt: Tuple[int, ...]
    max_new_tokens: int = 16
    temperature: float = 0.0
    seed: int = 0

    def __post_init__(self):
        object.__setattr__(self, "prompt",
                           tuple(int(t) for t in self.prompt))


@dataclasses.dataclass(frozen=True)
class Completion:
    request_id: int
    prompt_len: int
    tokens: Tuple[int, ...]        # generated ids only (prompt excluded)
    ttft_s: float                  # submit -> first token
    latency_s: float               # submit -> last token
    queue_wait_s: float = 0.0      # submit -> admission (slot + pages free)
    token_times: Tuple[float, ...] = ()  # perf_counter stamp per token


class _Record:
    __slots__ = ("rid", "request", "tokens", "submit_t", "first_tok_t",
                 "completion", "slot", "queue_wait", "token_times")

    def __init__(self, rid: int, request: Request, submit_t: float):
        self.rid, self.request, self.submit_t = rid, request, submit_t
        self.tokens: List[int] = []
        self.first_tok_t = 0.0
        self.completion: Optional[Completion] = None
        self.slot: Optional[int] = None
        self.queue_wait = 0.0
        self.token_times: List[float] = []


class RequestHandle:
    """Poll-able view of a submitted request."""

    def __init__(self, record: _Record):
        self._rec = record

    @property
    def request_id(self) -> int:
        return self._rec.rid

    def done(self) -> bool:
        return self._rec.completion is not None

    def result(self) -> Completion:
        if self._rec.completion is None:
            raise RuntimeError(f"request {self._rec.rid} not finished; "
                               "step()/drain() the engine first")
        return self._rec.completion

    @property
    def token_times(self) -> Tuple[float, ...]:
        """perf_counter stamp of every token emitted so far, finished or
        not (a finished request's equal its Completion's)."""
        return tuple(self._rec.token_times)


class _Inflight:
    """One dispatched-but-unretired decode macro-step: the stacked token
    futures plus a host-side snapshot of which slots emit how many tokens.
    The snapshot is fixed at dispatch (admissions after the dispatch join
    the NEXT macro-step), so retiring is pure bookkeeping."""
    __slots__ = ("toks", "snapshot", "k")

    def __init__(self, toks, snapshot, k: int):
        self.toks, self.snapshot, self.k = toks, snapshot, k


class _PrefillJob:
    """A multi-chunk prefill in flight: the request's slot and pages are
    reserved, its transient batch-1 state accumulates one chunk per
    engine step, and activation (scatter into the pool + first-token
    sample) happens when the last chunk lands."""
    __slots__ = ("rec", "state", "done")

    def __init__(self, rec: _Record, state):
        self.rec, self.state, self.done = rec, state, 0


# ---------------------------------------------------------------------------
# Jitted kernels: per-row sampling, bucketed/chunked prefill, pooled decode
# ---------------------------------------------------------------------------
def sample_logits(logits: jax.Array) -> jax.Array:
    """Prepare logits for sampling: float32, constrained replicated.

    The gumbel draw inside ``jax.random.categorical`` must see a
    replicated 32-bit consumer: under a mesh, GSPMD partitions a
    sub-32-bit (e.g. bfloat16) random draw along the vocab sharding of
    whatever consumes it, which CHANGES the bits relative to the eager /
    single-device draw — the one-shot path's eager first token and the
    engine's jitted prefill would sample different tokens from identical
    logits.  Replicated float32 keeps every sampling site — eager or
    jitted, one-shot or pooled decode — on the same random stream."""
    from ..models.common import shard
    return shard(logits.astype(jnp.float32), *([None] * logits.ndim))


def _sample_row(logits32: jax.Array, key: jax.Array, temp: jax.Array):
    """One row of serve._select on a ``sample_logits``-prepared row:
    split-then-categorical when sampling, argmax (key untouched) when
    greedy.  Only the temperature *value* is traced — both branches run
    and a where picks, so sweeping temperature (or mixing greedy/sampled
    slots in one batch) never retraces."""
    nxt, sub = jax.random.split(key)
    safe = jnp.where(temp > 0, temp, jnp.ones((), temp.dtype))
    cat = jax.random.categorical(sub, logits32 / safe)
    tok = jnp.where(temp > 0, cat, jnp.argmax(logits32, axis=-1))
    return tok.astype(jnp.int32), jnp.where(temp > 0, nxt, key)


_sample_rows = jax.vmap(_sample_row)


@functools.partial(jax.jit, static_argnames=("cfg", "max_len"))
def _prefill_one(params, prompt, valid_len, key, temp, *, cfg, max_len):
    """Prefill ONE right-padded prompt into a fresh batch-1 state and
    sample its first token.  Compiled once per (cfg, max_len, bucket
    length) — the bucket policy bounds how many of these exist."""
    PREFILL_TRACES[0] += 1
    state = lm.init_decode_state(cfg, 1, max_len)
    logits, state = lm.prefill(params, prompt, state, cfg, valid_len)
    tok, key = _sample_row(sample_logits(logits[:, -1])[0], key, temp)
    return tok, key, state


@functools.partial(jax.jit, static_argnames=("cfg", "seq_len"))
def _fresh_chunk_state(*, cfg, seq_len):
    """Transient batch-1 state for a chunked prefill, with attention K/V
    held in float32: chunk j must attend chunks < j at exactly the
    precision the one-shot prefill attends its fresh (pre-cache) K/V.
    The activation scatter rounds to the pool's cache dtype — the same
    single rounding the one-shot path applies when it writes its cache."""
    state = lm.init_decode_state(cfg, 1, seq_len)
    kv = paged_leaf_paths(cfg)
    return {lk: {k: (v.astype(jnp.float32)
                     if f"{lk}/{k}" in kv and v.dtype != jnp.int8 else v)
                 for k, v in layer.items()}
            for lk, layer in state.items()}


@functools.partial(jax.jit, static_argnames=("cfg",))
def _prefill_chunk(params, tokens, state, chunk_start, valid_len, *, cfg):
    """One prefill chunk against the carried transient state.  chunk_start
    and valid_len are traced, so ONE compiled program covers every chunk
    of every prompt at this (cfg, chunk length)."""
    PREFILL_TRACES[0] += 1
    return lm.prefill(params, tokens, state, cfg, valid_len,
                      chunk_start=chunk_start)


@jax.jit
def _first_token(logits, key, temp):
    """Sample the first token from a chunked prefill's final logits —
    the same `_sample_row(sample_logits(...))` composition _prefill_one
    runs fused, on the same materialized values."""
    return _sample_row(sample_logits(logits[:, -1])[0], key, temp)


@functools.partial(jax.jit, static_argnames=("cfg", "k"),
                   donate_argnums=(1, 2, 4))
def _decode_multi(params, pool, tok, pos, keys, temps, remaining,
                  page_table, *, cfg, k):
    """K fused decode micro-steps over the whole slot pool — ONE device
    dispatch per K tokens (``lm.decode_scan``), sampling in-scan.

    Per-slot RNG keys fold through ``_sample_rows`` exactly as the
    one-step path did (same split order, same replicated float32 logits),
    so the emitted stream is bit-identical to K dispatches of one step.
    ``remaining`` counts tokens each slot still owes; rows at 0 are
    frozen — token/position/key stop advancing mid-scan — which is how
    idle slots (always 0) and slots whose stop fires at micro-step j < K
    coexist with live rows in one program.  The pool tree and the
    token/key carries are donated: the engine immediately rebinds them to
    the returned arrays, so each output may take its input's buffer.
    Donation alone does not avoid a copy of the pool: ``lm.decode_step``
    writes each layer group's new state into the carried pool at the
    group's index, so the pool is updated in place in the donated buffer
    (a stacked scan output would be a fresh buffer copied whole into it
    every dispatch).

    The first returned block is (k + 1, C) int32: k rows of sampled
    tokens, then each slot's (token, held expert) pairs routed by the MoE
    layers over the k micro-steps, so the count comes back in the copy of
    the tokens the retire makes anyway."""
    DECODE_TRACES[0] += 1

    def sample(logits, aux):
        keys, temps, remaining = aux
        live = remaining > 0
        toks, nkeys = _sample_rows(sample_logits(logits), keys, temps)
        keys = jnp.where(live[:, None], nkeys, keys)
        remaining = jnp.where(live, remaining - 1, remaining)
        return toks, (keys, temps, remaining), live

    pool, tok, _, (keys, _, _), toks, live, routed = lm.decode_scan(
        params, pool, tok, pos, cfg, (keys, temps, remaining), sample, k,
        page_table=page_table)
    toks = jnp.concatenate([toks, routed.sum(0, keepdims=True)], 0)
    return toks, live, pool, tok, keys


@jax.jit
def _poke_slot(tok_arr, key_arr, slot, tok, key):
    """Write one activated slot's first token + folded key into the
    device-resident decode carries (keeps the steady-state loop free of
    host->device uploads of the full (C, .) arrays)."""
    return tok_arr.at[slot, 0].set(tok), key_arr.at[slot].set(key)


# ---------------------------------------------------------------------------
# EngineConfig: the one setup path
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class EngineConfig:
    """Source of truth for standing up a server (CLI flags mirror these
    fields).  See the module docstring for field semantics."""
    arch: str = "rwkv6-7b"
    epitome: str = "off"
    plan: Any = None                 # path str | EpitomePlan | None
    mesh: Optional[str] = ""         # '' auto-DP | 'D,M' sharded | None as-is
    smoke: bool = False
    prepack: bool = True
    capacity: int = 4
    max_len: int = 128
    page_size: int = 16              # KV page tokens; 0 = dense per-slot
    kv_pages: int = 0                # pool pages; 0 = capacity * pages/slot
    prefill_chunk: int = 64          # chunked-prefill tokens; 0 = whole
    decode_block: int = 1            # decode micro-steps fused per dispatch
    seed: int = 0

    def build(self) -> "EpimEngine":
        plan = self.plan or None
        if isinstance(plan, str):
            from ..pim.plan import EpitomePlan
            plan = EpitomePlan.load(plan)
        cfg = (get_smoke_config(self.arch, self.epitome, plan=plan)
               if self.smoke else
               get_config(self.arch, self.epitome, plan=plan))
        mesh = shard_mesh = None
        if self.mesh is not None:
            if self.mesh:
                data, model = parse_mesh(self.mesh)
                mesh = (mesh_for_plan(plan, data=data, model=model)
                        if plan is not None
                        else make_host_mesh(data=data, model=model))
                shard_mesh = mesh   # explicit mesh => lay params out on it
            else:
                mesh = make_host_mesh(data=len(jax.devices()))
            set_mesh(mesh)
        # independent streams for params / prompts / sampling (one shared
        # key would correlate the prompt draw with the weight init)
        init_key, prompt_key, sample_key = jax.random.split(
            jax.random.PRNGKey(self.seed), 3)
        params = lm.init_params(init_key, cfg)
        if shard_mesh is not None:
            # lay the params out before packing, so the packed tree reuses
            # the sharded E leaves: packing first would hold the whole
            # model, its codes and every shard on the first device at once
            # (more than one chip holds at real widths)
            params = lm.shard_params(params, cfg, shard_mesh)
        packed = (lm.prepack_params(params, cfg, mesh=shard_mesh)
                  if self.prepack and lm.needs_prepack(cfg) else None)
        engine = EpimEngine(cfg, packed if packed is not None else params,
                            capacity=self.capacity, max_len=self.max_len,
                            page_size=self.page_size, kv_pages=self.kv_pages,
                            prefill_chunk=self.prefill_chunk,
                            decode_block=self.decode_block)
        engine.config, engine.mesh = self, mesh
        engine.params, engine.packed = params, packed
        engine.prompt_key, engine.sample_key = prompt_key, sample_key
        return engine


# ---------------------------------------------------------------------------
# The engine
# ---------------------------------------------------------------------------
class EpimEngine:
    """Slot-scheduled continuous-batching server over one paged pool."""

    def __init__(self, cfg, serve_params, capacity: int = 4,
                 max_len: int = 128, page_size: int = 16,
                 kv_pages: int = 0, prefill_chunk: int = 64,
                 decode_block: int = 1):
        if capacity < 1:
            raise ValueError("capacity must be >= 1")
        if decode_block < 1:
            raise ValueError("decode_block must be >= 1")
        self.cfg, self.serve_params = cfg, serve_params
        self.capacity, self.max_len = capacity, max_len
        # a capacity dispatch couples every row of a batch-1 prefill (pad
        # tokens would take expert-queue ranks), so MoE prompts it would
        # serve prefill exact-length; the per-token path is row-local
        self.bucket_prompts = not moe.takes_dispatch(cfg, 1, max_len)
        self._pool = SlotStatePool(cfg, capacity, max_len,
                                   page_size=page_size, kv_pages=kv_pages)
        self.seq_len = self._pool.seq_len   # static prefill/decode KV rows
        # chunked prefill: aligned to the recurrence windows so chunk
        # boundaries are one-shot window boundaries (bit-exactness); off
        # under a capacity dispatch (token coupling) and for int8 caches
        # (chunk 2 would attend dequantized rows the one-shot path attends
        # fresh)
        if prefill_chunk > 0 and self.bucket_prompts \
                and cfg.kv_cache_bits != 8:
            align = recurrence_alignment(cfg)
            self.chunk = -(-prefill_chunk // align) * align
        else:
            self.chunk = 0
        self.decode_block = decode_block
        self._prefilling: Optional[_PrefillJob] = None
        self._chunks_left = 0            # per-step()/submit() chunk budget
        # device-resident decode carries: the sampled-token and RNG-key
        # rows never round-trip the host in steady state — macro-step k+1
        # is dispatched straight on macro-step k's output arrays
        self._tok = jnp.zeros((capacity, 1), jnp.int32)
        self._key = jnp.zeros((capacity, 2), jnp.uint32)
        self._pos = np.zeros((capacity,), np.int32)
        self._temp = np.zeros((capacity,), np.float32)
        self._inflight: Optional[_Inflight] = None
        self._free = list(range(capacity))[::-1]      # pop() -> slot 0 first
        self._used: set = set()
        self._active: Dict[int, _Record] = {}
        self._pending: deque = deque()
        self._records: List[_Record] = []
        self._next_id = itertools.count()
        self._stats = {"slot_reuses": 0, "decode_steps": 0,
                       "decode_micro_steps": 0, "decode_traces": 0,
                       "completed": 0, "admitted": 0,
                       "prefill_traces": 0, "prefill_chunks": 0,
                       "prefill_s": 0.0, "expert_rows": 0}
        # set by EngineConfig.build (None for a bare-constructed engine)
        self.config: Optional[EngineConfig] = None
        self.mesh = None
        self.params = self.packed = None
        self.prompt_key = self.sample_key = None

    # -- public API ---------------------------------------------------------
    @functools.partial(jax.profiler.annotate_function, name="epim.submit")
    def submit(self, request: Request) -> RequestHandle:
        P = len(request.prompt)
        if P < 1:
            raise ValueError("empty prompt")
        if request.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if not self.cfg.embed_inputs:
            bad = next((t for t in request.prompt
                        if not 0 <= t < self.cfg.vocab), None)
            if bad is not None:
                raise ValueError(f"prompt token id {bad} outside the "
                                 f"vocabulary [0, {self.cfg.vocab})")
        if P > self.max_len:
            raise ValueError(f"prompt length {P} exceeds the engine's "
                             f"max_len budget ({self.max_len})")
        if P + request.max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({P}) + max_new_tokens ({request.max_new_tokens}) "
                f"exceeds the engine's max_len ({self.max_len})")
        need = self._pool.pages_needed(P + request.max_new_tokens)
        if self._pool.paged and need > self._pool.page.num_pages:
            raise ValueError(
                f"request needs {need} KV pages but the pool holds only "
                f"{self._pool.page.num_pages} (kv_pages) — it could never "
                "be admitted")
        rec = _Record(next(self._next_id), request, time.perf_counter())
        self._records.append(rec)
        self._pending.append(rec)
        self._chunks_left = 1
        self._admit_all()
        return RequestHandle(rec)

    def step(self) -> int:
        """One pipelined engine tick: host-side work first (one prefill
        chunk + admissions — both overlap the macro-step the device is
        already computing), then retire that macro-step's outputs, then
        dispatch the next macro-step asynchronously.  Returns the number
        of decode tokens RETIRED this tick (0 = nothing was in flight).

        The double-buffering lives in the device-resident carries: the
        next dispatch consumes the previous dispatch's token/key output
        arrays directly, so the only host<->device traffic per tick is
        the small stacked-token download at retire — and that download
        happens after the next macro-step is already enqueued."""
        with jax.profiler.StepTraceAnnotation(
                "epim.step", step_num=self._stats["decode_steps"]):
            self._chunks_left = 1
            if self._prefilling is not None:
                self._advance_prefill()
            self._admit_all()
            emitted = self._retire()
            self._admit_all()              # slots/pages freed by _retire
            self._dispatch()
            return emitted

    def drain(self) -> List[Completion]:
        """Step until no request is pending, prefilling, active, or in
        flight; return every completion this engine has produced, in
        submission order."""
        while self._pending or self._active or self._prefilling \
                or self._inflight:
            self.step()
        return [r.completion for r in self._records
                if r.completion is not None]

    @property
    def stats(self) -> Dict[str, float]:
        return {**self._stats,
                "queue_depth": len(self._pending),
                **self._pool.stats()}

    @property
    def n_active(self) -> int:
        return len(self._active)

    @property
    def n_pending(self) -> int:
        return len(self._pending)

    # -- scheduler internals ------------------------------------------------
    def _pick_k(self) -> int:
        """Decode micro-steps to fuse into the next dispatch: the block
        size, clipped to the fewest remaining tokens among active slots —
        so no slot overshoots max_new_tokens (or, equivalently, the page
        reservation admission made for it) inside one macro-step."""
        left = min(rec.request.max_new_tokens - len(rec.tokens)
                   for rec in self._active.values())
        return max(1, min(self.decode_block, left))

    def _dispatch(self) -> None:
        """Launch the next decode macro-step asynchronously (no-op when
        nothing is active).  Host mirrors of position/remaining advance
        immediately — they are deterministic given the snapshot — while
        the sampled tokens stay on device until ``_retire``."""
        if not self._active or self._inflight is not None:
            return
        k = self._pick_k()
        with jax.profiler.TraceAnnotation("epim.dispatch", k=k,
                                          live=len(self._active)):
            remaining = np.zeros((self.capacity,), np.int32)
            snapshot = []
            for slot, rec in self._active.items():
                r = rec.request.max_new_tokens - len(rec.tokens)
                remaining[slot] = r
                snapshot.append((slot, rec, min(k, r)))
            base = DECODE_TRACES[0]
            toks, live, tree, tok, keys = _decode_multi(
                self.serve_params, self._pool.tree, self._tok,
                jnp.asarray(self._pos), self._key, jnp.asarray(self._temp),
                jnp.asarray(remaining), self._pool.page_table,
                cfg=self.cfg, k=k)
            self._stats["decode_traces"] += DECODE_TRACES[0] - base
            self._pool.tree = tree
            self._tok, self._key = tok, keys
            for slot, _, n in snapshot:
                self._pos[slot] += n
            self._stats["decode_steps"] += 1
            self._stats["decode_micro_steps"] += k
            self._inflight = _Inflight(toks, snapshot, k)

    def _retire(self) -> int:
        """Block on the in-flight macro-step's stacked tokens and do the
        host bookkeeping: append per-slot emissions, stamp times, finish
        (and free) slots that reached max_new_tokens.  Pages are freed
        HERE — the macro-step boundary — never mid-scan, so a slot whose
        stop fired at micro-step j < K holds its reservation until the
        step that computed it retires."""
        inf, self._inflight = self._inflight, None
        if inf is None:
            return 0
        with jax.profiler.TraceAnnotation("epim.retire"):
            with jax.profiler.TraceAnnotation("epim.retire.wait"):
                toks = np.asarray(jax.device_get(inf.toks))   # (k + 1, C)
            now = time.perf_counter()
            self._stats["expert_rows"] += int(toks[inf.k].sum())
            emitted = 0
            for slot, rec, n in inf.snapshot:
                for j in range(n):
                    rec.tokens.append(int(toks[j, slot]))
                    rec.token_times.append(now)
                emitted += n
                if len(rec.tokens) >= rec.request.max_new_tokens:
                    self._finish(rec)
            return emitted

    def _bucket(self, P: int) -> int:
        if not self.bucket_prompts:
            return P
        return min(max(8, 1 << (P - 1).bit_length()), self.seq_len)

    def _needs_chunking(self, P: int) -> bool:
        return bool(self.chunk) and P > self.chunk

    def _admit_all(self) -> None:
        # FIFO with head-of-line blocking: a deferred head (pages dry, or
        # an in-flight chunked prefill) holds everything behind it, which
        # keeps admission order — and therefore slot/page assignment —
        # a pure function of submission order.
        while self._pending and self._free and self._prefilling is None:
            rec = self._pending[0]
            req = rec.request
            if not self._pool.can_admit(len(req.prompt)
                                        + req.max_new_tokens):
                break                      # defer until pages free up
            if self._needs_chunking(len(req.prompt)) \
                    and self._chunks_left <= 0:
                break                      # chunk budget spent this step
            self._pending.popleft()
            self._admit(rec)

    def _admit(self, rec: _Record) -> None:
        slot = self._free.pop()
        with jax.profiler.TraceAnnotation("epim.admit", rid=rec.rid,
                                          slot=slot):
            self._stats["slot_reuses"] += slot in self._used
            self._used.add(slot)
            rec.slot = slot
            req = rec.request
            P = len(req.prompt)
            self._pool.alloc(slot, P + req.max_new_tokens)
            rec.queue_wait = time.perf_counter() - rec.submit_t
            if self._needs_chunking(P):
                state = _fresh_chunk_state(cfg=self.cfg,
                                           seq_len=self.seq_len)
                self._prefilling = _PrefillJob(rec, state)
                self._advance_prefill()
                return
            L = self._bucket(P)
            prompt = np.zeros((1, L), np.int32)
            prompt[0, :P] = req.prompt
            with self._prefill_span(rec, bucket=L):
                base = PREFILL_TRACES[0]
                tok, key, state = _prefill_one(
                    self.serve_params, jnp.asarray(prompt), jnp.int32(P),
                    jax.random.PRNGKey(req.seed),
                    jnp.float32(req.temperature),
                    cfg=self.cfg, max_len=self.seq_len)
                self._stats["prefill_traces"] += PREFILL_TRACES[0] - base
                self._activate(rec, state, tok, key)

    @contextlib.contextmanager
    def _prefill_span(self, rec: _Record, **where):
        """``epim.prefill`` around one prefill call (and, for the last, its
        activation up to the first token on the host), its host seconds
        added to ``stats["prefill_s"]``."""
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("epim.prefill", rid=rec.rid,
                                          **where):
            yield
        self._stats["prefill_s"] += time.perf_counter() - t

    def _advance_prefill(self) -> None:
        """Run ONE chunk of the in-flight chunked prefill (if any and if
        this step's chunk budget allows)."""
        job = self._prefilling
        if job is None or self._chunks_left <= 0:
            return
        self._chunks_left -= 1
        req = job.rec.request
        P = len(req.prompt)
        lo = job.done
        n = min(self.chunk, P - lo)
        buf = np.zeros((1, self.chunk), np.int32)
        buf[0, :n] = req.prompt[lo:lo + n]
        with self._prefill_span(job.rec, chunk=lo // self.chunk):
            base = PREFILL_TRACES[0]
            logits, job.state = _prefill_chunk(
                self.serve_params, jnp.asarray(buf), job.state,
                jnp.int32(lo), jnp.int32(n), cfg=self.cfg)
            self._stats["prefill_traces"] += PREFILL_TRACES[0] - base
            self._stats["prefill_chunks"] += 1
            job.done = lo + n
            if job.done < P:
                return
            tok, key = _first_token(logits, jax.random.PRNGKey(req.seed),
                                    jnp.float32(req.temperature))
            self._prefilling = None
            self._activate(job.rec, job.state, tok, key)

    def _activate(self, rec: _Record, state, tok, key) -> None:
        """Scatter a finished prefill into the pool, poke its first token
        and key into the decode carries, and go live once that token is
        on the host.  Both launches precede the wait, so the device runs
        them straight after the prefill while the host waits."""
        slot = rec.slot
        req = rec.request
        with jax.profiler.TraceAnnotation("epim.activate", rid=rec.rid,
                                          slot=slot):
            self._pool.scatter(slot, state)
            self._tok, self._key = _poke_slot(self._tok, self._key,
                                              jnp.int32(slot), tok, key)
        with jax.profiler.TraceAnnotation("epim.prefill.wait"):
            rec.tokens.append(int(jax.device_get(tok)))
        now = time.perf_counter()
        rec.first_tok_t = now
        rec.token_times.append(now)
        self._pos[slot] = len(req.prompt)
        self._temp[slot] = req.temperature
        self._stats["admitted"] += 1
        if req.max_new_tokens == 1:
            self._finish(rec)
        else:
            self._active[slot] = rec

    def _finish(self, rec: _Record) -> None:
        now = time.perf_counter()
        rec.completion = Completion(
            request_id=rec.rid, prompt_len=len(rec.request.prompt),
            tokens=tuple(rec.tokens), ttft_s=rec.first_tok_t - rec.submit_t,
            latency_s=now - rec.submit_t, queue_wait_s=rec.queue_wait,
            token_times=tuple(rec.token_times))
        self._active.pop(rec.slot, None)
        self._free.append(rec.slot)
        self._pool.free(rec.slot)
        self._pos[rec.slot] = 0
        self._stats["completed"] += 1
