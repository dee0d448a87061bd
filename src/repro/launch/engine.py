"""Continuous-batching serving engine: slot scheduler + scan-fused decode.

The paper's deployment story is PTQ inference through the PoFx datapath;
this module is the system that serves it under real traffic instead of the
old one-shot fixed-batch driver. Design (DESIGN.md §7):

* **Slots.** A fixed-slot batch of ``n_slots`` sequences shares one donated
  decode cache whose ``pos`` leaf is a per-slot (B,) length vector. Slots
  mask independently: ``decode_step`` rotates, writes KV and masks
  attention per slot, so requests of different ages coexist in one batch.
* **Admission.** A request is prefilled alone (batch 1, optionally padded
  to a length bucket to bound recompilation) and its cache scattered into
  a free slot along every leaf's batch axis (``LM.cache_logical`` names
  it). The first token is sampled from the prefill logits.
* **Decode.** ``chunk`` steps run as ONE jitted ``lax.scan`` — no
  per-step Python dispatch. Per-slot stopping (EOS / max-new-tokens)
  freezes a finished slot inside the chunk: its pos stops advancing and it
  emits pad tokens until the host retires it and admits the next request.
* **Sampling.** Greedy (temperature 0), temperature, and top-k compose
  per slot from (B,) parameter vectors; each slot folds its own PRNG key
  with its position, so a request's sample stream is reproducible
  regardless of batch composition — eviction + re-admission resumes the
  identical stream.
* **Eviction.** ``evict`` returns a running request to the pending queue
  with its generated prefix folded into the context; re-admission prefills
  prompt+prefix and continues. Scheduler invariants are tested in
  tests/test_engine.py.

``use_kernel`` is decided by the ``LM`` the engine wraps
(``build_model(..., use_kernel=True)``), so quantized serving exercises
the fused Pallas PoFx/FxP kernels end to end. So is the KV-cache format
(``build_model(..., kv_spec=...)``, DESIGN.md §8): with a quantized cache
the slot cache's "k"/"v" leaves hold byte-wide codes next to static
per-channel scale leaves, and the scatter/evict/resume machinery below is
layout-agnostic — admission scatters code+scale leaves along the batch
axis ``LM.cache_logical`` names, and eviction's re-prefill regenerates the
identical codes (static scales + fake-quant prefill), so the
resume-identical guarantee survives the lossy cache.

So, finally, is tensor parallelism (DESIGN.md §9): a model built over the
1-D ``("tp",)`` serving mesh (``build_model(..., mesh=make_tp_mesh(N))``)
makes the engine device_put parameters and the slot cache sharded —
attention heads, MLP hidden, experts, and the KV cache's head axis (codes
AND static scales) split over tp — and run prefill + the chunked decode
scan inside ``shard_map`` with the model's ``manual_tp`` twin (explicit
one-psum-per-block collectives). Tokens, slot keys, sampling params and
``pos`` stay replicated, so every scheduler decision below — admit, evict,
resume, per-slot stopping — is device-count-agnostic and the served token
streams are the single-device streams.

**Paged mode** (``paged=True``, DESIGN.md §10): the per-slot dense cache is
replaced by one flat pool of fixed-size token pages plus per-slot block
tables; ``launch.paging.PagedKVManager`` owns allocation, refcounts and the
radix prefix index on the host. Admission matches the context against the
index and prefills only the unshared suffix (the shared prefix — system
prompts, resumed generations — is already resident); eviction registers
the sequence's pages in the index and drops its references, so resume
re-attaches surviving pages and re-prefills exactly one token. The token
streams stay identical to the dense engine's (the paged differential
contract, tests/test_paged_cache.py): page contents are a deterministic
function of the token prefix under the pool's global static scales, and
the suffix prefill attends to [shared prefix ; suffix] with the same
kv-chunk boundaries a dense full prefill would use.
"""
from __future__ import annotations

import dataclasses
import functools
import time
from collections import deque
from typing import Any, Deque, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from .tracing import SpanTotals

__all__ = ["SamplingParams", "Request", "RequestState", "ServeEngine",
           "sample_tokens"]

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs; all compose per slot inside the scan."""
    temperature: float = 0.0     # 0 = greedy (argmax)
    top_k: int = 0               # 0 = no truncation


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    prompt: np.ndarray           # (P,) int token ids
    max_new: int = 32            # tokens to generate (incl. prefill-sampled)
    sampling: SamplingParams = SamplingParams()
    arrival: float = 0.0         # virtual time (decode steps) of arrival


@dataclasses.dataclass
class RequestState:
    req: Request
    context: np.ndarray          # tokens to prefill (prompt, +prefix on resume)
    slot: int = -1
    out: List[int] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None   # "eos" | "length"
    admitted_at: float = -1.0
    finished_at: float = -1.0
    n_evictions: int = 0
    submitted_s: float = -1.0    # host clock (perf_counter) at submit
    admitted_s: float = -1.0     # host clock at the start of its first
    #   admission

    @property
    def done(self) -> bool:
        return self.finish_reason is not None


def sample_tokens(logits: jax.Array, keys: jax.Array, temps: jax.Array,
                  topks: jax.Array, use_topk: bool = True) -> jax.Array:
    """Pluggable per-slot sampling: greedy / temperature / top-k.

    logits (B, V); keys (B,) PRNG keys; temps (B,) float (0 = greedy);
    topks (B,) int (0 = full distribution). Greedy slots ignore their key,
    so free slots can carry stale keys safely. ``use_topk=False`` (a
    static promise that every topk is 0) skips the O(V log V) sort — the
    engine sets it per chunk so temperature-only serving never pays for
    top-k in the hot loop.
    """
    V = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1)
    if use_topk:
        k = jnp.clip(topks, 1, V)
        sorted_lg = jnp.sort(logits, axis=-1)         # ascending
        kth = jnp.take_along_axis(sorted_lg, (V - k)[:, None], axis=-1)
        filt = jnp.where((topks[:, None] > 0) & (logits < kth), NEG_INF,
                         logits)
    else:
        filt = logits
    scaled = filt / jnp.maximum(temps, 1e-6)[:, None]
    sampled = jax.vmap(jax.random.categorical)(keys, scaled)
    return jnp.where(temps > 0, sampled, greedy).astype(jnp.int32)


class ServeEngine:
    """Slot-based continuous batching over one ``LM`` + quantized params.

    Host side owns scheduling (pending queue, slot occupancy, token
    streams); device side owns the batch state (cache, last tokens, slot
    keys). Each ``step`` call launches one jitted scan of ``chunk`` decode
    steps; admission happens between chunks.
    """

    def __init__(self, model, params, *, n_slots: int = 4, max_len: int = 512,
                 eos_id: Optional[int] = None, pad_id: int = 0,
                 chunk: int = 8, prompt_bucket: int = 1, seed: int = 0,
                 paged: bool = False, page_size: int = 16,
                 n_pages: Optional[int] = None):
        if model.cfg.family == "encdec":
            raise NotImplementedError(
                "encdec serving needs per-request encoder frames; use the "
                "one-shot path in repro.launch.serve")
        if prompt_bucket > 1 and model.cfg.family in ("ssm", "hybrid"):
            raise ValueError(
                "prompt_bucket > 1 right-pads prefill, which pollutes SSM "
                "recurrent state; use exact-length prefill (bucket 1)")
        if n_slots < 1 or chunk < 1:
            raise ValueError(
                f"need n_slots >= 1 and chunk >= 1, got {n_slots}/{chunk}")
        self.model, self.params = model, params
        self.n_slots, self.max_len = int(n_slots), int(max_len)
        self.eos_id = eos_id
        self.pad_id = int(pad_id)
        self.chunk = int(chunk)
        self.prompt_bucket = max(1, int(prompt_bucket))
        self.paged = bool(paged)

        # Tensor parallelism: a model built over the ("tp",) serving mesh
        # serves sharded. ``_mm`` is the model the jitted device functions
        # call — the manual_tp twin inside shard_map, the model itself
        # otherwise. Scheduler state below never looks at tp.
        self.tp = model.tp_size
        self._mm = model.manual_tp() if self.tp > 1 else model
        self._mesh = model.ctx.mesh if self.tp > 1 else None

        if self.paged:
            # Paged KV cache (DESIGN.md §10): one flat page pool + per-slot
            # block tables; the host-side manager owns allocation/refcounts
            # and the radix prefix index. Default pool sizing matches the
            # dense cache's capacity (every slot can hold max_len tokens)
            # plus per-slot headroom for copy-on-write and index retention.
            from repro.core.policy import format_spec
            from .paging import PagedKVManager
            self.page_size = int(page_size)
            self.max_pages = -(-self.max_len // self.page_size)
            if n_pages is None:
                n_pages = n_slots * self.max_pages + n_slots + 1
            self.n_pages = int(n_pages)
            self.cache = model.init_paged_cache(
                n_slots, max_len, n_pages=self.n_pages,
                page_size=self.page_size)
            kv = (self.cache["kv"]["moe"] if "moe" in self.cache["kv"]
                  else self.cache["kv"])
            # pages are shareable only between consumers of one cache
            # format: the index keys on the spec string (or raw dtype)
            spec_key = (format_spec(model.kv_spec) if model.kv_spec
                        else f"raw:{kv['k'].dtype}")
            self._pager = PagedKVManager(self.n_pages, self.page_size,
                                         self.max_pages, spec_key)
            self._slot_pos = np.zeros(n_slots, np.int64)
            self._cache_log_flat = jax.tree_util.tree_flatten(
                model.paged_cache_logical(),
                is_leaf=lambda x: isinstance(x, tuple))[0]
        else:
            self.cache = model.init_cache(n_slots, max_len)
            self.cache["pos"] = jnp.zeros((n_slots,), jnp.int32)
            self._cache_log_flat = jax.tree_util.tree_flatten(
                model.cache_logical(),
                is_leaf=lambda x: isinstance(x, tuple))[0]
        n_leaves = len(jax.tree_util.tree_leaves(self.cache))
        if n_leaves != len(self._cache_log_flat):
            # scatter zips cache leaves against logical axes positionally;
            # a silent mismatch (e.g. a cache layout that grew leaves —
            # quantized caches add scale leaves — without a cache_logical
            # update) would mis-scatter instead of erroring
            raise ValueError(
                f"cache has {n_leaves} leaves but cache_logical names "
                f"{len(self._cache_log_flat)}; LM.init_cache and "
                "LM.cache_logical disagree")
        if self.tp > 1:
            self._param_specs = model.param_tp_specs(params)
            self._cache_specs = model.cache_tp_specs(self.cache)
            if not self.paged:
                self._small_specs = model.cache_tp_specs(
                    jax.eval_shape(lambda: model.init_cache(1, self.max_len)))
            put = lambda tree, specs: jax.device_put(
                tree, jax.tree.map(
                    lambda s: NamedSharding(self._mesh, s), specs))
            self.params = put(self.params, self._param_specs)
            self.cache = put(self.cache, self._cache_specs)
        self._tok = jnp.full((n_slots, 1), self.pad_id, jnp.int32)
        self._base_key = jax.random.PRNGKey(seed)
        # placeholder slot keys (replaced at admit; fold stream disjoint
        # from per-request keys, which fold non-negative rids)
        filler = jax.random.fold_in(self._base_key, np.uint32(0xFFFFFFFF))
        self._keys = jnp.stack(
            [jax.random.fold_in(filler, i) for i in range(n_slots)])

        # host-side scheduler state
        self._slot_rid = np.full(n_slots, -1, np.int64)
        self._states: Dict[int, RequestState] = {}
        self._pending: Deque[int] = deque()
        self._done_box: List[RequestState] = []
        self.clock = 0.0              # virtual time = decode steps executed
        self.decode_steps = 0
        self.n_prefill_sampled = 0    # tokens sampled from prefill logits
        #   (one per admission, so one per request plus one per eviction —
        #    the exact complement of decode-generated tokens)
        # running counters reported by stats(), which must stay O(1): the
        # benchmark's client reads it between engine calls
        self.generated_tokens = 0
        self.live_slot_steps = 0      # slot-steps that decoded a token
        self.live_context_sum = 0     # their attended lengths, summed
        self.admissions = 0
        self.admitted_prompt_tokens = 0
        self.prefill_tokens = 0       # run through prefill (shared prefix
        #   excluded)
        self.n_chunks = 0
        self._spans = SpanTotals()

        self._chunk_fn = jax.jit(
            self._chunk_wrap,
            static_argnames=("steps", "eos", "pad", "greedy_only",
                             "topk_any"),
            donate_argnums=(1,))
        if self.paged:
            self._prefill_paged_fn = jax.jit(
                self._prefill_paged_wrap, static_argnames=("prefix_len",),
                donate_argnums=(1,))
            self._copy_page_fn = jax.jit(self._copy_page_impl,
                                         donate_argnums=(0,))
        else:
            self._scatter_fn = jax.jit(self._scatter_impl, donate_argnums=(0,))
            self._prefill_fn = jax.jit(self._prefill_wrap, donate_argnums=(1,))
        self._sample_fn = jax.jit(self._sample_impl)
        # the engine's jitted device programs, whose compiled variants
        # stats() counts
        self._programs = {"decode_chunk": self._chunk_fn,
                          "sample": self._sample_fn}
        if self.paged:
            self._programs.update(prefill_paged=self._prefill_paged_fn,
                                  copy_page=self._copy_page_fn)
        else:
            self._programs.update(prefill=self._prefill_fn,
                                  scatter=self._scatter_fn)

    # -- scheduler (host) ----------------------------------------------------

    @property
    def decode_time(self) -> float:
        """Host seconds from launching each decode chunk to holding its
        tokens (``serve.step.launch`` + ``serve.step.read``)."""
        return (self._spans.seconds("serve.step.launch")
                + self._spans.seconds("serve.step.read"))

    @property
    def prefill_time(self) -> float:
        """Host seconds admitting requests (``serve.admit``): plan, prefill
        and the wait for the first token."""
        return self._spans.seconds("serve.admit")

    @property
    def free_slots(self) -> List[int]:
        return [b for b in range(self.n_slots) if self._slot_rid[b] < 0]

    @property
    def active_rids(self) -> List[int]:
        return [int(r) for r in self._slot_rid if r >= 0]

    @property
    def pending_rids(self) -> List[int]:
        return list(self._pending)

    def submit(self, req: Request) -> None:
        if req.rid in self._states:
            raise ValueError(f"duplicate request id {req.rid}")
        prompt = np.asarray(req.prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError(f"request {req.rid}: empty prompt")
        if prompt.size >= self.max_len:
            raise ValueError(
                f"request {req.rid}: prompt length {prompt.size} >= "
                f"max_len {self.max_len}")
        if req.max_new < 1:
            raise ValueError(f"request {req.rid}: max_new must be >= 1")
        self._states[req.rid] = RequestState(
            req=req, context=prompt, submitted_s=time.perf_counter())
        self._pending.append(req.rid)

    def request(self, rid: int) -> RequestState:
        """The state of request ``rid``: its tokens so far, slot, finish
        reason and host-clock submit / first-admission times."""
        return self._states[rid]

    def evict(self, rid: int) -> None:
        """Preempt a running request back to the head of the pending queue.

        Its generated prefix folds into the context, so re-admission
        prefills prompt+prefix and resumes the identical sample stream
        (slot keys fold with absolute position).
        """
        st = self._states[rid]
        if st.slot < 0 or st.done:
            raise ValueError(f"request {rid} is not running")
        st.context = np.concatenate(
            [np.asarray(st.req.prompt, np.int32).reshape(-1),
             np.asarray(st.out, np.int32)])
        if self.paged:
            # register the sequence's pages in the prefix index, then drop
            # its references: surviving pages make resume a one-token
            # prefill (the index match re-attaches them), and a genuinely
            # evicted (reclaimed) page just re-prefills like dense mode.
            # Valid tokens = written KV positions = the slot's pos (the
            # final sampled token was emitted but its KV never written).
            self._release_slot_pages(rid, st)
        self._slot_rid[st.slot] = -1
        st.slot = -1
        st.n_evictions += 1
        self._pending.appendleft(rid)

    def admit_ready(self) -> int:
        """Admit arrived pending requests into free slots; returns count.

        Scans the whole queue (FIFO among arrived), not just the head: a
        manually-submitted queue need not be arrival-ordered, and a
        not-yet-arrived head must not block an already-arrived request
        behind it (that would livelock ``run``'s idle fast-forward).
        """
        n = 0
        while self.free_slots:
            rid = next((r for r in self._pending
                        if self._states[r].req.arrival <= self.clock), None)
            if rid is None:
                break
            self._pending.remove(rid)
            self._admit(rid, self.free_slots[0])
            n += 1
        return n

    def _eff_max_new(self, st: RequestState) -> int:
        """max_new clamped so decode never writes past max_len."""
        room = self.max_len - int(np.asarray(st.req.prompt).size)
        return min(st.req.max_new, room)

    def _admit_paged(self, rid: int, slot: int) -> None:
        """Paged admission: match the prefix index, attach shared pages,
        prefill only the unshared suffix through the page pool.

        The host manager plans everything (borrowed pages, copy-on-write
        of a mid-page boundary, fresh allocations); the device executes
        the plan: CoW pool copies, the slot's block-table row, then a
        batch-1 suffix prefill whose attention spans [shared prefix ;
        suffix] — sampled logits match a dense full prefill's, so the
        admission is stream-identical to the dense engine's.
        """
        st = self._states[rid]
        ctx = st.context
        P = int(ctx.size)
        Pb = min(-(-P // self.prompt_bucket) * self.prompt_bucket,
                 self.max_len)
        with self._admit_span(st, P) as admit:
            with self._spans.span("serve.admit.plan"):
                # prompt_bucket > 1 means the operator asked for bounded
                # prefill compile variants — page-align the prefix hit too,
                # since each distinct prefix_len is a fresh compile
                # (exact-length serving, bucket 1, keeps token-granular
                # sharing and recompiles per length, exactly like dense
                # prefill does)
                plan = self._pager.admit(rid, ctx.tolist(), Pb,
                                         page_align=self.prompt_bucket > 1)
                prefix_len = int(plan.prefix_len)
                for src, dst in plan.copies:
                    self.cache = self._copy_page_fn(
                        self.cache, jnp.asarray(src, jnp.int32),
                        jnp.asarray(dst, jnp.int32))
                self.cache["pages"] = self.cache["pages"].at[slot].set(
                    jnp.asarray(plan.table))
            admit.annotation.set_metadata(prefix_tokens=prefix_len)
            n_suffix = P - prefix_len
            padded = np.full((1, Pb - prefix_len), self.pad_id, np.int32)
            padded[0, :n_suffix] = ctx[prefix_len:]
            with self._spans.span("serve.admit.prefill"):
                self.cache, logits = self._prefill_paged_fn(
                    self.params, self.cache, jnp.asarray(padded),
                    jnp.asarray(slot, jnp.int32),
                    jnp.asarray(n_suffix, jnp.int32), prefix_len=prefix_len)
            # index the prompt's pages so concurrent/later requests with the
            # same system prompt skip its prefill (content is final: writes
            # past P only ever touch offsets beyond the registered valid run)
            self._pager.register(rid, ctx.tolist(), P)
            self._slot_pos[slot] = P
            tok0 = self._first_token(rid, slot, P, logits)
        self._publish(rid, slot, tok0, n_suffix)

    def _admit(self, rid: int, slot: int) -> None:
        if self.paged:
            return self._admit_paged(rid, slot)
        st = self._states[rid]
        ctx = st.context
        P = int(ctx.size)
        # bucket-rounded length clamped to the cache: prefill writes Pb KV
        # positions, and a resumed context may sit close to max_len
        Pb = min(-(-P // self.prompt_bucket) * self.prompt_bucket,
                 self.max_len)
        padded = np.full((1, Pb), self.pad_id, np.int32)
        padded[0, :P] = ctx
        with self._admit_span(st, P, prefix_tokens=0):
            with self._spans.span("serve.admit.plan"):
                small = self.model.init_cache(1, self.max_len)
                small = self._seed_kv_scales(small, slot)
            # bucket 1 means exact-length prompts: take the length=None path
            # so SSM/hybrid (which refuse right-padded prefill) serve too.
            length = None if Pb == P else jnp.asarray(P, jnp.int32)
            with self._spans.span("serve.admit.prefill"):
                small, logits = self._prefill_fn(
                    self.params, small, jnp.asarray(padded), length)
            with self._spans.span("serve.admit.scatter"):
                self.cache = self._scatter_fn(self.cache, small,
                                              jnp.asarray(slot, jnp.int32))
            tok0 = self._first_token(rid, slot, P, logits)
        self._publish(rid, slot, tok0, P)

    def _admit_span(self, st: RequestState, P: int, **ids):
        if st.admitted_s < 0:
            st.admitted_s = time.perf_counter()
        return self._spans.span("serve.admit", rid=st.req.rid,
                                prompt_tokens=P, **ids)

    def _first_token(self, rid: int, slot: int, P: int, logits) -> int:
        """Sample the first token from the prefill logits (key folds the
        ABSOLUTE position P-1, so paged and dense admissions draw the
        identical stream), set the slot's token and key, and wait for
        them: the admission's only waits on the device."""
        st = self._states[rid]
        with self._spans.span("serve.admit.read"):
            key = jax.random.fold_in(self._base_key, rid)
            st0 = st.req.sampling
            tok0 = self._sample_fn(
                logits, jax.random.fold_in(key, P - 1)[None],
                jnp.asarray([st0.temperature], jnp.float32),
                jnp.asarray([st0.top_k], jnp.int32))
            tok0 = int(tok0[0])
            self._tok = self._tok.at[slot, 0].set(tok0)
            self._keys = self._keys.at[slot].set(key)
            jax.block_until_ready(self._tok)
        return tok0

    def _publish(self, rid: int, slot: int, tok0: int, prefilled: int
                 ) -> None:
        """Shared admission tail: publish slot state, count the admission,
        retire immediately on eos/length."""
        st = self._states[rid]
        self._slot_rid[slot] = rid
        st.slot = slot
        if st.admitted_at < 0:
            st.admitted_at = self.clock
        st.out.append(tok0)
        self.generated_tokens += 1
        self.n_prefill_sampled += 1
        self.admissions += 1
        self.admitted_prompt_tokens += int(st.context.size)
        self.prefill_tokens += int(prefilled)
        if self.eos_id is not None and tok0 == self.eos_id:
            self._finish(rid, "eos")
        elif len(st.out) >= self._eff_max_new(st):
            self._finish(rid, "length")

    def _release_slot_pages(self, rid: int, st: RequestState) -> None:
        """Index the slot's pages (full pages + partial tail) for future
        prefix hits, return the sequence's references to the allocator,
        and point the slot's block-table row at the garbage page so the
        retired slot's zombie decode writes (it still rides in the batch
        until the next admission) cannot touch a live page."""
        slot = st.slot
        tokens = np.concatenate(
            [np.asarray(st.req.prompt, np.int32).reshape(-1),
             np.asarray(st.out, np.int32)])
        self._pager.suspend(rid, tokens.tolist(), int(self._slot_pos[slot]))
        self.cache["pages"] = self.cache["pages"].at[slot].set(
            jnp.zeros((self.max_pages,), jnp.int32))

    def _finish(self, rid: int, reason: str) -> None:
        st = self._states[rid]
        st.finish_reason = reason
        st.finished_at = self.clock
        if st.slot >= 0:
            if self.paged:
                self._release_slot_pages(rid, st)
            self._slot_rid[st.slot] = -1
            st.slot = -1
        self._done_box.append(st)

    # -- device chunk --------------------------------------------------------

    def _prefill_wrap(self, params, cache, tokens, length):
        """Prefill, shard_map-wrapped when serving tensor-parallel.

        Inside the shard_map every device prefills with its local weight /
        KV-head shard (one psum per block); tokens, length and logits are
        replicated. ``length`` may be None (exact-length prompts) — an
        empty pytree, which shard_map broadcasts a spec over harmlessly.
        """
        with jax.named_scope("serve_prefill"):
            if self.tp == 1:
                return self.model.prefill(params, tokens, cache=cache,
                                          length=length)
            from repro.nn.sharding import shard_map_compat
            mm = self._mm
            fn = lambda p, c, t, l: mm.prefill(p, t, cache=c, length=l)
            rep = P()
            return shard_map_compat(
                fn, self._mesh,
                in_specs=(self._param_specs, self._small_specs, rep, rep),
                out_specs=(self._small_specs, rep),
            )(params, cache, tokens, length)

    def _prefill_paged_wrap(self, params, cache, tokens, slot, length, *,
                            prefix_len: int):
        """Paged suffix prefill, shard_map-wrapped when tensor-parallel:
        the page pools ride in/out as head shards, the block tables / slot
        / length / logits replicate. ``prefix_len`` is static (it fixes
        gather sizes and the attention bias offset), so each distinct
        shared-prefix length compiles once."""
        with jax.named_scope("serve_prefill_paged"):
            if self.tp == 1:
                return self.model.prefill_paged(params, tokens, cache=cache,
                                                slot=slot, length=length,
                                                prefix_len=prefix_len)
            from repro.nn.sharding import shard_map_compat
            mm = self._mm
            fn = lambda p, c, t, s, l: mm.prefill_paged(
                p, t, cache=c, slot=s, length=l, prefix_len=prefix_len)
            rep = P()
            return shard_map_compat(
                fn, self._mesh,
                in_specs=(self._param_specs, self._cache_specs, rep, rep,
                          rep),
                out_specs=(self._cache_specs, rep),
            )(params, cache, tokens, slot, length)

    def _copy_page_impl(self, cache, src, dst):
        """Copy page ``src`` -> ``dst`` in every pool code leaf (the
        device half of copy-on-write; scales are global per layer, nothing
        to copy). The pool axis is found from the paged logical tree, so
        moe's extra layer-stacking dims need no special-casing."""
        flat, treedef = jax.tree_util.tree_flatten(cache)
        out = []
        with jax.named_scope("serve_copy_page"):
            for leaf, ax in zip(flat, self._cache_log_flat):
                if "kv_pages" not in ax:
                    out.append(leaf)
                    continue
                axis = ax.index("kv_pages")
                page = jax.lax.dynamic_index_in_dim(leaf, src, axis,
                                                    keepdims=False)
                out.append(jax.lax.dynamic_update_index_in_dim(
                    leaf, page, dst, axis))
        return jax.tree_util.tree_unflatten(treedef, out)

    def _chunk_wrap(self, params, cache, tok, done, n_gen, keys, temps,
                    topks, max_new, *, steps: int, eos: int, pad: int,
                    greedy_only: bool, topk_any: bool):
        """The scan-fused chunk, shard_map-wrapped when tensor-parallel.

        The whole ``steps``-long decode scan runs inside ONE shard_map:
        params and the slot cache stay resident as shards, the per-slot
        token/done/pos/sampling state is replicated (every device runs the
        identical sampler on identical psum'd logits), so the emitted
        tokens are bit-identical to the tp=1 scan's by construction of the
        replicated compute — the property tests/test_tp_engine.py pins.
        """
        impl = functools.partial(self._chunk_impl, steps=steps, eos=eos,
                                 pad=pad, greedy_only=greedy_only,
                                 topk_any=topk_any)
        with jax.named_scope("serve_decode_chunk"):
            if self.tp == 1:
                return impl(params, cache, tok, done, n_gen, keys, temps,
                            topks, max_new)
            from repro.nn.sharding import shard_map_compat
            rep = P()
            return shard_map_compat(
                impl, self._mesh,
                in_specs=(self._param_specs, self._cache_specs,
                          rep, rep, rep, rep, rep, rep, rep),
                out_specs=(self._cache_specs, rep, rep, rep, rep),
            )(params, cache, tok, done, n_gen, keys, temps, topks, max_new)

    def _seed_kv_scales(self, small, slot: int):
        """Copy the target slot's static KV scale leaves into the batch-1
        prefill cache. Scales are calibration state (per-model constants,
        DESIGN.md §8), not per-request state: init_cache resets them to
        1.0, so without this an operator's calibrated scales would drive
        neither the admit prefill nor — after the scatter writes the
        batch-1 leaves back — any later decode on that slot."""
        if self.model.kv_spec is None:
            return small
        flat, treedef = jax.tree_util.tree_flatten_with_path(small)
        big_flat = jax.tree_util.tree_flatten(self.cache)[0]
        out = []
        for (path, s), b, ax in zip(flat, big_flat, self._cache_log_flat):
            name = getattr(path[-1], "key", None)
            if isinstance(name, str) and name.endswith("_scale"):
                axis = ax.index("batch")
                s = jax.lax.dynamic_slice_in_dim(b, slot, 1, axis=axis)
            out.append(s)
        return jax.tree_util.tree_unflatten(treedef, out)

    def _scatter_impl(self, big, small, slot):
        """Write a batch-1 prefilled cache into slot ``slot`` of the big
        cache, leaf by leaf along the axis ``cache_logical`` names "batch"
        (pos, logical (), is per-slot scalar)."""
        big_flat, treedef = jax.tree_util.tree_flatten(big)
        small_flat = jax.tree_util.tree_flatten(small)[0]
        out = []
        with jax.named_scope("serve_scatter"):
            for b, s, ax in zip(big_flat, small_flat, self._cache_log_flat):
                if ax == ():
                    out.append(b.at[slot].set(
                        jnp.ravel(jnp.asarray(s))[0].astype(b.dtype)))
                else:
                    axis = ax.index("batch")
                    upd = jax.lax.index_in_dim(s, 0, axis=axis,
                                               keepdims=False)
                    out.append(jax.lax.dynamic_update_index_in_dim(
                        b, upd.astype(b.dtype), slot, axis))
        return jax.tree_util.tree_unflatten(treedef, out)

    @staticmethod
    def _sample_impl(logits, keys, temps, topks):
        with jax.named_scope("serve_sample"):
            return sample_tokens(logits, keys, temps, topks)

    def _chunk_impl(self, params, cache, tok, done, n_gen, keys, temps,
                    topks, max_new, *, steps: int, eos: int, pad: int,
                    greedy_only: bool, topk_any: bool):
        """``steps`` decode iterations as one lax.scan; per-slot stopping.

        A slot that emits EOS (or hits max_new) freezes: pos stops
        advancing, later emissions are pad. The emitted-token semantics
        mirror the host loop in ``step`` exactly. ``greedy_only`` (static,
        host-known per chunk) skips the top-k sort + categorical draw in
        the hot loop when every live slot has temperature 0 — argmax is
        exactly what sample_tokens returns there.
        """
        model = self._mm    # the manual_tp twin when serving tensor-parallel

        def body(carry, _):
            cache, tok, done, n_gen = carry
            pos = cache["pos"]
            cache, logits = model.decode_step(params, cache, tok)
            if greedy_only:
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            else:
                step_keys = jax.vmap(jax.random.fold_in)(keys, pos)
                nxt = sample_tokens(logits, step_keys, temps, topks,
                                    use_topk=topk_any)
            stop = (nxt == eos) | (n_gen + 1 >= max_new)
            nxt = jnp.where(done, pad, nxt)
            n_gen = jnp.where(done, n_gen, n_gen + 1)
            new_done = done | stop
            cache = dict(cache, pos=jnp.where(done, pos, pos + 1))
            return (cache, nxt[:, None], new_done, n_gen), nxt

        (cache, tok, done, n_gen), toks = jax.lax.scan(
            body, (cache, tok, done, n_gen), None, length=steps)
        return cache, tok, done, n_gen, toks      # toks: (steps, B)

    # -- chunk driver (host) -------------------------------------------------

    def step(self, steps: Optional[int] = None) -> List[RequestState]:
        """Run one scan-fused chunk; returns requests finished in it.

        The chunk is capped by the largest remaining per-slot budget so a
        tail chunk doesn't scan steps in which every slot is frozen — but
        the cap rounds up to a power of two, because ``steps`` is a static
        jit argument and every distinct value recompiles the whole scan:
        pow2 rounding bounds wasted tail work below 2x useful steps while
        bounding compile variants at log2(chunk) instead of chunk.
        """
        steps = int(steps or self.chunk)
        B = self.n_slots
        live = self._slot_rid >= 0
        if not live.any():
            return []
        self.n_chunks += 1
        with self._spans.step("serve.step", self.n_chunks - 1,
                              live=int(live.sum())) as chunk:
            with self._spans.span("serve.step.inputs"):
                n_gen = np.zeros(B, np.int32)
                temps = np.zeros(B, np.float32)
                topks = np.zeros(B, np.int32)
                max_new = np.full(B, np.iinfo(np.int32).max, np.int32)
                for b, rid in enumerate(self._slot_rid):
                    if rid < 0:
                        continue
                    st = self._states[rid]
                    n_gen[b] = len(st.out)
                    temps[b] = st.req.sampling.temperature
                    topks[b] = st.req.sampling.top_k
                    max_new[b] = self._eff_max_new(st)
                eos = self.eos_id if self.eos_id is not None else -1
                rem = int((max_new[live] - n_gen[live]).max())
                steps = min(steps, 1 << max(rem - 1, 0).bit_length())
                if self.paged:
                    # allocate page coverage for every live slot's
                    # worst-case chunk advance BEFORE the scan runs
                    # device-side (allocation is host state; a mid-chunk
                    # page-boundary crossing cannot call out)
                    for b, rid in enumerate(self._slot_rid):
                        if rid < 0:
                            continue
                        row = self._pager.ensure(
                            rid, min(int(self._slot_pos[b]) + steps,
                                     self.max_len))
                        if row is not None:
                            self.cache["pages"] = self.cache["pages"].at[
                                b].set(jnp.asarray(row))
            chunk.annotation.set_metadata(steps=steps)
            with self._spans.span("serve.step.launch"):
                self.cache, self._tok, _, _, toks = self._chunk_fn(
                    self.params, self.cache, self._tok, jnp.asarray(~live),
                    jnp.asarray(n_gen), self._keys, jnp.asarray(temps),
                    jnp.asarray(topks), jnp.asarray(max_new),
                    steps=steps, eos=int(eos), pad=self.pad_id,
                    greedy_only=bool((temps == 0).all()),
                    topk_any=bool((topks > 0).any()))
            with self._spans.span("serve.step.read"):
                toks = np.asarray(toks)          # blocks; (steps, B)
            self.decode_steps += steps
            self.clock += steps
            with self._spans.span("serve.step.emit"):
                return self._emit(toks, steps, n_gen)

    def _emit(self, toks: np.ndarray, steps: int, n_gen: np.ndarray
              ) -> List[RequestState]:
        """Hand each live slot's chunk tokens to its request up to its
        stop, retire finished requests, and count the slot-steps that
        decoded a token and the context each attended."""
        finished: List[RequestState] = []
        for b, rid in enumerate(self._slot_rid):
            if rid < 0:
                continue
            st = self._states[rid]
            limit = self._eff_max_new(st)
            for s in range(steps):
                t = int(toks[s, b])
                st.out.append(t)
                if self.paged:
                    # mirror the device: pos advances once per emitted
                    # token (the final-token step advances, then freezes),
                    # and must be current before _finish releases pages
                    self._slot_pos[b] += 1
                if self.eos_id is not None and t == self.eos_id:
                    self._finish(rid, "eos")
                    break
                if len(st.out) >= limit:
                    self._finish(rid, "length")
                    break
            # the chunk's first step wrote the KV of the slot's last token,
            # at position len(prompt) + n_gen - 1, and attended one more
            n = len(st.out) - int(n_gen[b])
            pos = int(np.asarray(st.req.prompt).size) + int(n_gen[b]) - 1
            self.generated_tokens += n
            self.live_slot_steps += n
            self.live_context_sum += n * pos + n * (n + 1) // 2
            if st.done:
                finished.append(st)
        return finished

    def run(self, requests: Sequence[Request],
            chunk: Optional[int] = None) -> List[RequestState]:
        """Serve a workload to completion; returns states sorted by rid.

        Arrival times are in decode steps of virtual time; the clock
        advances by each chunk's step count and fast-forwards over idle
        gaps, so arrival mixes are reproducible independent of wall speed.
        """
        for r in sorted(requests, key=lambda r: (r.arrival, r.rid)):
            self.submit(r)
        with self._spans.span("serve.run"):
            while self._pending or self.active_rids:
                self.admit_ready()
                if not self.active_rids:
                    nxt = min(self._states[rid].req.arrival
                              for rid in self._pending)
                    self.clock = max(self.clock, nxt)
                    continue
                self.step(chunk)
        done, self._done_box = self._done_box, []
        return sorted(done, key=lambda s: s.req.rid)

    # -- reporting -----------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        """Running totals; O(1) in the requests served and the size of the
        prefix index. ``spans`` maps each span name to its count and host
        seconds (``launch/tracing.py``)."""
        # one token per *admission* comes from prefill logits (so one per
        # request plus one per eviction/resume); the rest are decode steps
        out = {
            "requests": len(self._states),
            "generated_tokens": self.generated_tokens,
            "prefill_sampled_tokens": self.n_prefill_sampled,
            "decode_tokens": self.generated_tokens - self.n_prefill_sampled,
            "decode_steps": self.decode_steps,
            "prefill_time_s": self.prefill_time,
            "decode_time_s": self.decode_time,
            "live_slot_steps": self.live_slot_steps,
            "live_context_sum": self.live_context_sum,
            "admissions": self.admissions,
            "admitted_prompt_tokens": self.admitted_prompt_tokens,
            "prefill_tokens": self.prefill_tokens,
            "programs": {name: fn._cache_size()
                         for name, fn in self._programs.items()},
            "spans": self._spans.report(),
        }
        if self.paged:
            # prefix_hit_tokens = prefill tokens skipped via shared pages;
            # resident_pages counts live pool pages (slots + index)
            out.update(self._pager.stats())
        return out

