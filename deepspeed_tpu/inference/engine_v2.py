"""Inference engine v2: continuous batching over a paged KV cache.

Reference parity: ``InferenceEngineV2`` (``inference/v2/engine_v2.py:30``) and
``build_hf_engine`` (``engine_factory.py:70``). The reference schedules ragged
batches through persistent CUDA kernels with host/device shadow buffers; here
every decode step is one fixed-shape jit program over all sequence slots —
inactive slots read the trash block, write nothing and are ignored — so continuous
batching costs zero recompiles and XLA keeps the MXU busy with the batched
GEMMs. Prefill runs per-sequence at bucketed lengths (one compile per bucket).

Speculative decoding (``inference.speculative.*``, default OFF —
docs/serving.md): a model-free prompt-lookup drafter proposes up to k tokens
per live sequence from the request's own prompt+output history; ONE batched
forward pass over the paged cache verifies every draft position
(``_verify_fn`` — the ctx-offset prefill machinery reused at decode time);
the longest agreeing prefix is accepted — exact rejection sampling against
the ``sampling.py`` distributions for non-greedy requests — and rejected KV
positions are rolled back with ``StateManager.truncate``. Decode-bound
serving then emits >1 token per model step without a second model.
"""

from __future__ import annotations

import time
from collections import Counter, deque
from itertools import repeat
from typing import (Any, Callable, Dict, Iterator, List, NamedTuple, Optional,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np

from ..comm.mesh import MeshManager
from ..models._paged import MixedCall
from ..ops.quantization import kv_dequantize_int8, kv_quantize_int8
from ..telemetry.compile import CompileMonitor
from ..telemetry.schema import DRAIN_CAUSES
from ..telemetry.trace import percentiles
from ..utils.logging import log_dist
from .config import InferenceConfig
from .engine import InferenceEngine, ModelFamily, _round_up
from .ragged import (IndexPoolError, KVKindError, LatentKVError,  # noqa: F401
                     RecurrentStateError, StateManager, UnknownSequenceError,
                     WindowKind)  # (re-exports)
from .sampling import (SamplingParams, accept_drafts, sample, sample_batch,
                       sp_arrays)


def prompt_lookup_draft(history, max_tokens: int, ngram_max: int = 3,
                        min_match: int = 1) -> List[int]:
    """Prompt-lookup (n-gram) drafting: match the TRAILING n-gram of
    ``history`` (n from ``ngram_max`` down to ``min_match``) against an
    earlier occurrence and propose up to ``max_tokens`` of the tokens that
    followed it — the most recent occurrence wins. Model-free: the "draft
    model" is the request's own prompt + generated output, which makes it
    free to run and strongest exactly where decode is most wasteful
    (repetitive continuations, quoted context, multi-turn echoes). Returns
    ``[]`` when nothing matches — the caller falls back to plain decode."""
    n_hist = len(history)
    if max_tokens <= 0 or n_hist < max(1, min_match) + 1:
        return []
    arr = np.asarray(history, np.int32)
    for n in range(min(ngram_max, n_hist - 1), max(1, min_match) - 1, -1):
        pat = arr[n_hist - n:]
        # windows over arr[:-1]: every match start i has i + n <= n_hist - 1,
        # so at least one continuation token exists (and the trailing n-gram
        # can never match itself)
        win = np.lib.stride_tricks.sliding_window_view(arr[:n_hist - 1], n)
        hits = np.flatnonzero((win == pat).all(axis=1))
        if hits.size:
            start = int(hits[-1]) + n
            return arr[start:start + max_tokens].tolist()
    return []


# what ``InferenceEngineV2.last_step`` holds before a step has done anything
_NO_WORK = {"prefill_tokens": 0, "prefill_kv_tokens": 0, "decode_seqs": 0,
            "kv_tokens": 0}

# the one static sampling config the programs are built with: every
# greedy-equivalent request canonicalizes to it
_GREEDY = SamplingParams(greedy=True)

# What a kind of cache state refuses: a row a kind a family's cache may hold
# beside its K and V blocks or in their place (the error it raises, then
# {what is refused: why},
# in the order tried), a column a configuration feature, refused at
# construction (``_refuse_features``), or a call, refused when it is made
# (``_refuse_call``: ``fork``, and ``handoff``, the disagg block export /
# import). A column a row does not have is served.
_SPILLS = "it spills and restores prefix-cache blocks"
_REFUSALS = {
    "recurrent_state": (RecurrentStateError, {
        "inference.prefix_cache":
            "a cached prefix's blocks hold its keys and values (none at "
            "all in a family with no KV cache) but not the recurrent state "
            "at its end, and no snapshot of that state is kept",
        "inference.prefix_cache.host_spill": _SPILLS,
        "inference.speculative":
            "a rejected draft is rolled back by truncating blocks, and "
            "a recurrent state cannot be rolled back",
        "inference.kv_quant": "the family's cache has no quantized mode",
        "fork":
            "the child shares whatever KV blocks the parent holds, and the "
            "parent's recurrent state would have to be copied into a slot "
            "of its own, which is not written",
        "handoff":
            "a sequence's blocks are not its whole state, and the "
            "destination resumes through the prefix cache"}),
    # a family that declares recurrent state AND experts (``state_slot_bytes``
    # and ``moe_rows``: models/nemotron_h.py). A state family without experts
    # is served over a tensor mesh with its mixers whole on every device, as
    # it was (models/granite_hybrid.py ``param_logical_axes``)
    "state_and_experts": (RecurrentStateError, {
        "inference.tensor_parallel":
            "beside an expert bank a state-space mixer's heads, and the "
            "state pool's rows with them, would have to be split over the "
            "tensor axis the bank is split over: a mesh over such a family "
            "is not written"}),
    # each feature that names the two pools it knows, or that no test holds
    # over three
    "index_pool": (IndexPoolError, {
        "inference.kv_quant": "the index keys' pool has no quantized mode",
        "inference.prefix_cache":
            "a retained prefix's blocks would have to keep their index "
            "keys too, and nothing checks that they do",
        "inference.prefix_cache.host_spill": _SPILLS,
        "inference.speculative":
            "a rejected draft's index keys would have to be rolled "
            "back with its keys and values, and nothing checks that "
            "they are",
        "handoff":
            "the wire format carries the K and V pools alone, and a block "
            "without its index keys selects from zeros"}),
    # each feature that takes a sequence's state to be every block it ever
    # wrote (a rollback past the window is refused in ``StateManager``)
    "window_kinds": (KVKindError, {
        "inference.prefix_cache":
            "a retained prefix would have to keep the window layers' "
            "blocks the sequence gave back",
        "inference.prefix_cache.host_spill": _SPILLS,
        "inference.speculative":
            "a rejected draft is rolled back by truncating blocks, and "
            "nothing checks a rollback against the blocks given back",
        "inference.kv_quant": "the window kind's pool has no quantized mode",
        "fork":
            "a child shares its parent's blocks, and a window kind's are "
            "given back under the one that moves ahead",
        "handoff":
            "the wire format carries the full kind's blocks alone, and the "
            "window layers' state would be missing"}),
    # each feature that reads the K and V leaves by name, or that no test
    # holds over one pool (prefix reuse and ``fork`` are served: the block
    # lifecycle carries any leaf with the block axis)
    "latent_kind": (LatentKVError, {
        "inference.kv_quant":
            "the latent pool has no quantized mode (a row's latent and its "
            "roped key would want scales of their own)",
        "inference.prefix_cache.host_spill": _SPILLS
            + ", and nothing checks a restored latent block",
        "inference.speculative":
            "a rejected draft is rolled back by truncating blocks, and "
            "nothing checks a rollback over the latent pool",
        "handoff":
            "the wire format carries the K and V pools, and this cache "
            "has neither"}),
}


# how a slot's row of a decode-shaped program finds its token (``_slot_src``;
# resolved on the device by ``_own_tokens``): the host's copy, the slot's entry
# of the newest launched program's result, or that result's LAST entry - the
# first token of the prompt whose final chunk that program ran
_FROM_HOST, _FROM_SLOT, _FROM_CHUNK = 0, -1, -2


def _own_tokens(prev, seat):
    """The slots' token row, built inside the program: ``seat`` [slots] is
    the host's row - a token where the host seated the slot, else one of the
    two negative codes above - and ``prev`` [slots + 1] the token result of
    the program launched before this one, which the host may not have read
    yet (``InferenceEngineV2.launch``)."""
    b = seat.shape[0]
    return jnp.where(seat >= 0, seat,
                     jnp.where(seat == _FROM_CHUNK, prev[b], prev[:b]))


class _Chunk(NamedTuple):
    """The oldest pending split prefill's next chunk (``_next_chunk``)."""
    uid: int
    desc: Any
    sp: SamplingParams          # as given at ``put_split``
    tokens: np.ndarray          # the chunk's real tokens
    ctx: int                    # the sequence's tokens already cached
    final: bool                 # it ends the prompt
    width: int                  # the program's chunk rows (padding included)

    def arrays(self, table, recurrent: bool) -> Tuple:
        """(tokens ``[1, width]``, real tokens, context offset, block
        table, then the sequence's slot for a family with recurrent state):
        what a program takes of one chunk."""
        padded = np.zeros((1, self.width), np.int32)
        padded[0, :len(self.tokens)] = self.tokens
        return (padded, np.int32(len(self.tokens)), np.int32(self.ctx),
                table) + ((np.int32(self.desc.slot),) if recurrent else ())


class _Flight(NamedTuple):
    """A launched decode-shaped program whose tokens the host has not read
    (``launch`` appends, ``_read`` lands them)."""
    toks: Any                   # its result on the device, [slots + 1]
    live: Tuple                 # the sequences that had a decode row
    chunk: Optional[_Chunk]     # the chunk it ran (final: toks[-1] is the
                                # prompt's first token)
    t0: Optional[int]           # a mixed step's dispatch time, for the
                                # request's ring record; else None
    seq: int                    # the launch's number (``_next_seq``)


def _last_real(lengths):
    """The index of each sequence's last REAL row, traced: ``lengths`` [n]
    give [n] (0 for a dummy row of length 0); a chunk's scalar length gives
    a scalar."""
    return jnp.maximum(lengths - 1, 0)


def _sampler(rows: bool):
    """A program samples one of two ways, chosen from what its call can see
    (its sequences' params): an all-greedy call takes the argmax - no per-row
    sort machinery in the program - and any stochastic request switches the
    call to per-row (temperature, top_k, top_p, greedy) arrays as traced
    arguments, which compile ONCE for every mix of client configs (keying a
    program on a non-greedy ``sp`` would compile per distinct config).
    Returns ``pick(key, logits, *arrays)`` over logits [B, V], or over one
    row's [V] under ``vmap`` with the row's own key."""
    if not rows:
        return lambda key, logits: sample(key, logits, _GREEDY)

    def pick(key, logits, *arrays):
        if logits.ndim == 1:        # sample_batch works on a batch of rows
            return sample_batch(key, logits[None],
                                *(a[None] for a in arrays))[0]
        return sample_batch(key, logits, *arrays)

    return pick


class InferenceEngineV2(InferenceEngine):
    """put()/step() continuous batching; also exposes a high-level
    ``generate`` that drains a prompt list through the scheduler."""

    def __init__(self, family: ModelFamily, params: Any,
                 config: Optional[InferenceConfig] = None,
                 mesh_mgr: Optional[MeshManager] = None,
                 init_paged_cache: Optional[Callable] = None,
                 apply_paged: Optional[Callable] = None,
                 telemetry_hub=None):
        # a hub with an ENABLED tracer shares its flight recorder (serving
        # spans land next to training/checkpoint spans); otherwise the
        # engine's own config.trace block governs the ring (base class)
        hub_tracer = getattr(telemetry_hub, "tracer", None)
        if hub_tracer is not None and not hub_tracer.enabled:
            hub_tracer = None
        super().__init__(family, params, config, mesh_mgr, tracer=hub_tracer)
        rc = self.config.ragged
        pc = self.config.prefix_cache
        self._apply_paged = apply_paged
        self._init_paged = init_paged_cache
        self._hub = telemetry_hub
        if self._apply_paged is None:  # resolve from the family's module
            import deepspeed_tpu.models.llama as _llama  # default family
            self._apply_paged = _llama.apply_paged
            self._init_paged = _llama.init_paged_cache
        max_blocks_per_seq = max(
            2, (self.family.cfg.max_seq_len + rc.block_size - 1) // rc.block_size)
        # --- recurrent state (docs/serving.md "Recurrent state"): a family
        # that declares it (``state_slot_bytes``) keeps one fixed-size row a
        # sequence slot a recurrent layer - beside its KV blocks, or, where
        # its cache has no leaf with a block axis, as ALL a sequence holds
        # ("A state and no cache": no block is allocated, counted or walked,
        # and a free slot is the whole admission check). What treats a
        # sequence's state as its blocks is refused here or at its call.
        self._recurrent = self.family.state_slot_bytes is not None
        slot_kw = {"slots": rc.max_tracked_sequences} \
            if self._recurrent else {}
        self._blockless = self._recurrent and not (
            set(jax.eval_shape(lambda: self._init_paged(
                self.family.cfg, 2, rc.block_size, **slot_kw)))
            - set(self.family.state_leaves))
        # --- a learned token selection (docs/serving.md "Learned token
        # selection"): the family's cache has a third block pool, the index
        # keys'. The block lifecycle carries it as it carries any leaf with
        # the block axis (copy-on-write, fork); what names the pools it
        # knows is refused, here or at its call.
        self._indexed = bool(self.family.sparse_rows
                             and self.family.sparse_rows(self.family.cfg, ()))
        # --- kinds of KV state (docs/serving.md "Kinds of KV state"): a
        # family with sliding-window layers keeps their keys and values in a
        # pool of their own, sized from what bounds it - the slots, the
        # window, the most tokens one call writes for a sequence (a
        # SplitFuse chunk; the whole context without chunking) and the
        # block size - and gives a block back once it lies behind the
        # window. ``memory_config_blocks`` stays the full kind's count.
        self._window = dict(self.family.window_kinds(self.family.cfg)) \
            if self.family.window_kinds else {}
        # --- a latent (MLA) cache (docs/serving.md "Latent (MLA) cache"):
        # the family's ONE pool, ``cache["latent"]``, sized by
        # ``memory_config_blocks`` as the K and V pools are
        self._latent = dict(self.family.latent_kind(self.family.cfg)) \
            if self.family.latent_kind else {}
        # what those kinds refuse: a feature here, a call when it is made
        self._refusals = [_REFUSALS[kind] for kind, has in (
            ("recurrent_state", self._recurrent),
            ("state_and_experts",
             self._recurrent and self.family.moe_rows is not None),
            ("index_pool", self._indexed),
            ("window_kinds", self._window),
            ("latent_kind", self._latent)) if has]
        self._refuse_features()
        kinds = ()
        if self._window:
            call = _round_up(self.config.split_prefill_chunk,
                             self.config.prefill_bucket) \
                if self.config.split_prefill_chunk > 0 \
                else self.family.cfg.max_seq_len
            kinds = tuple(
                WindowKind.sized(name, window, rc.max_tracked_sequences,
                                 call, rc.block_size)
                for name, window in self._window.items())
            slot_kw["window_blocks"] = {k.name: k.num_blocks for k in kinds}
        self.state = StateManager(
            rc.max_tracked_sequences,
            # (the allocator wants its trash block and one more; neither is
            # a device byte where no leaf has a block axis)
            2 if self._blockless else rc.memory_config_blocks, rc.block_size,
            1 if self._blockless else max_blocks_per_seq,
            blockless=self._blockless, prefix_cache=pc.enabled,
            max_retained_blocks=pc.max_retained_blocks,
            state_slot_bytes=self.family.state_slot_bytes(
                self.family.cfg) if self._recurrent else 0,
            window_kinds=kinds)
        # --- quantized KV cache (inference.kv_quant; docs/serving.md
        # "Quantized KV cache"). Default OFF → the cache pytree, every
        # compiled paged program, and the token streams are byte-identical
        # to the bf16 engine (pinned by parity tests). When ON, the block
        # pools store int8 codes + fp32 per-block-per-group scales; the
        # scales are cache LEAVES with the block axis in the same position,
        # so COW copies (_copy_block_fn), host spill (_spill_read_block /
        # _spill_write_fn), fork, and spec-decode truncate all carry codes
        # AND scales through the existing block-lifecycle machinery.
        kq = getattr(self.config, "kv_quant", None)
        self._kvq_on = bool(kq is not None and kq.enabled)
        self._kvq_group = 0
        if self._kvq_on:
            if kq.dtype != "int8":
                raise ValueError(
                    f"inference.kv_quant.dtype {kq.dtype!r} is not wired — "
                    f"only 'int8' is supported")
            hd = self.family.cfg.head_size
            eff = min(int(kq.group_size), hd)
            if eff < 1 or hd % eff:
                raise ValueError(
                    f"inference.kv_quant.group_size {kq.group_size} does "
                    f"not divide head_size {hd}")
            self._kvq_group = eff
            try:
                self.cache = self._init_paged(
                    self.family.cfg, rc.memory_config_blocks, rc.block_size,
                    kv_quant_group=eff)
            except TypeError:
                raise ValueError(
                    "this model's init_paged_cache does not accept "
                    "kv_quant_group — the family has no quantized KV path; "
                    "disable inference.kv_quant") from None
        else:
            self.cache = self._init_paged(self.family.cfg,
                                          rc.memory_config_blocks,
                                          rc.block_size, **slot_kw)
        # commit the fresh pool to the mesh the way every paged program
        # hands it back: a fresh uncommitted array has another sharding
        # than a program's output, so the first program to touch it would
        # compile once for the fresh pool and again for every later call
        # (the serving twin of the scalar placement in runtime/engine.py)
        self.cache = jax.device_put(self.cache, self.mesh_mgr.replicated())
        self._kv_bytes = sum(
            leaf.nbytes for name, tree in self.cache.items()
            if name not in self.family.state_leaves
            for leaf in jax.tree.leaves(tree))
        self._paged_fns: Dict[Tuple, Callable] = {}
        # --- host-spill tier for evicted prefix-cache blocks
        # (inference.prefix_cache.host_spill; docs/memory.md). Default OFF →
        # the eviction path is exactly the pre-spill one. When ON, evicted
        # unreferenced blocks copy D2H (async, on the tier transfer worker)
        # into a HostKVPool keyed by chain hash, and admit_prompt restores
        # them into fresh device blocks on a prefix hit.
        self._kv_spill = None
        if pc.enabled and getattr(pc, "host_spill", False):
            from ..memory import HostKVPool, TransferWorker

            self._tier_worker = TransferWorker(name="dstpu-kv-spill")
            self._kv_spill = HostKVPool(
                max_blocks=int(getattr(pc, "max_spilled_blocks", -1)),
                worker=self._tier_worker)
            self.state.enable_host_spill(self._kv_spill,
                                         self._spill_read_block,
                                         self._spill_write_block)
        # persistent device-side slot state
        B = rc.max_tracked_sequences
        self._slot_tokens = np.zeros((B,), np.int32)   # the host's copy
        self._slot_lens = np.zeros((B,), np.int32)
        # --- one program in flight (docs/serving.md "One program in
        # flight"): ``launch`` dispatches a step's program and ``collect``
        # reads its tokens, and a scheduler tick launches program n+1 BEFORE
        # it collects program n, so the host's part of a tick runs under the
        # device. The one thing program n+1 needs of n's result - each
        # slot's last token - stays on the device: ``_prev`` is the newest
        # launched program's token result ([slots + 1], zeros before the
        # first: ONE shape and ONE placement whichever program produced it,
        # so no program compiles twice), ``_slot_src`` says which slots take
        # their token from it, ``_flight`` holds the launched programs the
        # host has not read, and ``_out`` the tokens read and not yet handed
        # to a caller.
        self._prev = jax.device_put(jnp.zeros((B + 1,), jnp.int32),
                                    self.mesh_mgr.replicated())
        self._slot_src = np.zeros((B,), np.int32)
        self._flight: deque = deque()
        self._out: Dict[int, List[int]] = {}
        # --- a program's life on one clock (docs/observability.md "A
        # launched program is one chain"): every program the engine launches
        # takes the next number (``_next_seq``; from 1, never reused), which
        # is the ``seq`` of its launch span and of the ``engine_wait`` that
        # reads it, wherever that nests. A drain - a read of everything in
        # flight ahead of the tick's own ``collect``, by what needs a
        # token's value or moves a sequence - is counted by its cause.
        self._seq = 0
        self.drains: Dict[str, int] = dict.fromkeys(DRAIN_CAUSES, 0)
        self._slot_tables = np.zeros((B, self.state.table_width), np.int32)
        # per-slot sampling params, recorded at admission — decode honors
        # these (the reference's v2 engine carries per-request sampling)
        self._slot_sp: List[SamplingParams] = [_GREEDY] * B
        # uid → (full prompt, SamplingParams from put_split)
        self._pending_prefill: Dict[int, Tuple] = {}
        # --- speculative decoding (docs/serving.md). Default OFF: step()
        # runs the exact pre-spec programs and none of the hooks below fire.
        sc = self.config.speculative
        self._spec_on = bool(sc.enabled)
        self._spec_k = max(1, int(sc.max_draft_tokens))
        self._spec_ngram_max = max(1, int(sc.ngram_max))
        self._spec_min_match = max(1, int(sc.min_match))
        # cumulative Serving/spec/* counters (spec_events): model steps run
        # in spec mode split into verify (>=1 draft scored) vs plain decode
        # fallbacks, plus drafted/accepted/emitted/rolled-back token counts
        # and verify-batch occupancy (valid positions / batch capacity)
        self.spec_stats: Dict[str, int] = {
            "verify_steps": 0, "decode_steps": 0, "step_seqs": 0,
            "drafted_tokens": 0, "accepted_tokens": 0, "emitted_tokens": 0,
            "rolled_back_tokens": 0, "verify_positions": 0,
            "verify_capacity": 0}
        # --- request-lifecycle tracing + latency SLO stats (trace.py;
        # docs/serving.md), on the ring of ``self.tracer``. Default OFF:
        # every ``_req_*`` hook below is a no-op and no timer ever starts.
        # (The dispatch spans are not gated: they cost a microsecond each
        # and a profiler session records them, ring or no ring.)
        self._trace_on = self.tracer.enabled
        # --- counters at the dispatch boundaries, public and read-only:
        # what the last step()/step_many() did (prompt tokens whose KV it
        # wrote, the KV positions its prefill calls and the one-shot
        # prefills admitted since the previous step attended over - what
        # the flash kernel walked, where the table's width is what it did
        # not -, sequences in its decode batch, the KV tokens that batch
        # attends over after the step), and the prompt tokens whose KV any
        # call wrote since construction (put/put_many prefill included,
        # prefix-cache hits not: nothing is written for them)
        self.last_step: Dict[str, int] = dict(_NO_WORK)
        self._admitted_kv_tokens = 0   # one-shot prefills since the last step
        # their ssm_rows / ssm_tokens (a family with recurrent state)
        self._admitted_ssm: Dict[str, int] = {}
        self.prefill_tokens_written = 0
        # steps, those that ran their chunk and their decodes as one program
        # and those launched while the program before was still unread
        # (``_launch_decode``; ``Serving/engine/{steps, mixed_steps,
        # overlapped_steps}``)
        self.steps = 0
        self.mixed_steps = 0
        self.overlapped_steps = 0
        # the token rows the launched forward programs ran and the rows
        # their heads scored (``_row_args``; ``Serving/engine/{rows,
        # head_rows}``)
        self.rows = 0
        self.head_rows = 0
        # what the learned selection did, ONE layer's count, cumulative
        # (``Serving/sparse/*``): rows that selected, the cached tokens they
        # scored, the tokens attention then read
        self.sparse_stats: Dict[str, int] = {
            "rows": 0, "ctx_scored": 0, "kv_selected": 0}
        # --- recompilation sentinel + per-program MFU attribution
        # (telemetry/compile.py; docs/observability.md). A hub with an
        # ENABLED monitor is shared — serving programs land in the same
        # registry as the training entry points; otherwise the engine's own
        # ``compile_monitor`` config block governs. Default OFF: every
        # paged program is the plain jax.jit object (bit-identical serving,
        # pinned by parity tests).
        hub_cm = getattr(telemetry_hub, "compile", None)
        if hub_cm is not None and getattr(hub_cm, "enabled", False):
            self.compile_monitor = hub_cm
        else:
            self.compile_monitor = CompileMonitor(
                getattr(self.config, "compile_monitor", None),
                tracer=self.tracer)
        self._req: Dict[int, dict] = {}   # uid → open lifecycle record
        # uid → fleet TraceContext adopted from a router (telemetry/fleet.py):
        # the next _req_admit for that uid joins the router's cross-replica
        # trace instead of minting a private one. Empty unless a router with
        # the obs plane enabled feeds it — the default path never writes it.
        self._adopted: Dict[int, Any] = {}
        self._lat: Dict[str, List[float]] = {
            "ttft_ms": [], "itl_ms": [], "queue_ms": [], "e2e_ms": []}
        spec_lbl = "on(k=%d)" % self._spec_k if self._spec_on else "off"
        blocks = "no KV blocks" if self._blockless else \
            f"{rc.memory_config_blocks} blocks × {rc.block_size} tokens"
        log_dist(f"InferenceEngineV2: {blocks}, {B} sequence slots, "
                 f"kv_quant={'int8(g=%d)' % self._kvq_group if self._kvq_on else 'off'}, "
                 f"prefix_cache={'on' if pc.enabled else 'off'}, "
                 f"recurrent_state={'%d B/slot' % self.state.state_slot_bytes if self._recurrent else 'none'}, "
                 f"speculative={spec_lbl}, "
                 f"trace={'on' if self._trace_on else 'off'}")

    # ------------------------------------------------------------------ #
    # request-lifecycle accounting: admit → queue-wait → prefill (chunks) →
    # per-decode-token → finish. Each request is one trace id; TTFT, ITL,
    # queue time, and e2e latency accumulate for the SLO percentiles.
    # ------------------------------------------------------------------ #
    def adopt_trace(self, uid: int, ctx) -> None:
        """Join a router-minted cross-replica trace (a
        :class:`~..telemetry.fleet.TraceContext`): the next admission of
        ``uid`` opens a ``replica_leg`` span under the router's root request
        span instead of minting a private trace — so the full lifecycle,
        re-homes included, exports as ONE Perfetto trace. No-op with
        tracing off."""
        if self._trace_on and ctx is not None:
            self._adopted[uid] = ctx

    def release_trace(self, uid: int, reason: str = "rehome") -> None:
        """Cross-replica hand-off: this engine is giving ``uid`` up (drain /
        failover re-home), so close its open lifecycle spans — otherwise
        they would never end and never reach the flight-recorder ring — but
        record NO latency samples (the destination leg owns the stream's SLO
        story). Tolerant of an absent record, like ``_req_drop``."""
        rec = self._req_close(uid)
        if rec is not None:
            rec["span"].end(handoff=reason)

    def _req_close(self, uid: int) -> Optional[dict]:
        """Take ``uid``'s lifecycle record out (None when there is none)
        with its queue-wait ended; the caller ends the request's span."""
        self._adopted.pop(uid, None)
        rec = self._req.pop(uid, None)
        if rec is not None and rec["queue"] is not None:
            rec["queue"].end()
        return rec

    def _req_admit(self, uid: int, prompt_len: int,
                   split: bool = False) -> None:
        if not self._trace_on or uid in self._req:
            return
        now = time.monotonic_ns()
        ctx = self._adopted.pop(uid, None)
        if ctx is not None:
            tid = ctx.trace_id
            span = self.tracer.begin("replica_leg", cat="serving", trace=tid,
                                     parent=ctx.parent_span, uid=uid,
                                     prompt_tokens=prompt_len, split=split,
                                     replica=ctx.replica)
        else:
            tid = self.tracer.new_trace(label=f"request:{uid}")
            span = self.tracer.begin("request", cat="serving", trace=tid,
                                     uid=uid, prompt_tokens=prompt_len,
                                     split=split)
        queue = self.tracer.begin("queue_wait", cat="serving", trace=tid,
                                  parent=span.span_id, uid=uid)
        self._req[uid] = {"trace": tid, "span": span, "queue": queue,
                          "t_admit": now, "last_ns": None,
                          "first_done": False}

    def _req_compute_begin(self, uid: int) -> None:
        """First compute dispatched for this request — queue-wait ends."""
        rec = self._req.get(uid)
        if rec is None or rec["queue"] is None:
            return
        rec["queue"].end()
        rec["queue"] = None
        self._lat["queue_ms"].append(
            (time.monotonic_ns() - rec["t_admit"]) / 1e6)

    def _req_first_token(self, uid: int, t_ns: int) -> None:
        rec = self._req.get(uid)
        if rec is None or rec["first_done"]:
            return
        if rec["queue"] is not None:   # fork children never prefill
            rec["queue"].end()
            rec["queue"] = None
            self._lat["queue_ms"].append((t_ns - rec["t_admit"]) / 1e6)
        rec["first_done"] = True
        rec["last_ns"] = t_ns
        self._lat["ttft_ms"].append((t_ns - rec["t_admit"]) / 1e6)
        self.tracer.instant("first_token", cat="serving", trace=rec["trace"],
                            parent=rec["span"].span_id, ts_ns=t_ns, uid=uid)

    def _req_tokens(self, uid: int, k: int, t_ns: int) -> None:
        """``k`` decode tokens for ``uid`` landed at ``t_ns`` (one fused
        quantum): ITL per token = elapsed / k. (No per-token instant: 32 a
        tick would turn the flight recorder over in two minutes and push
        out the spans a crash dump is kept for; the gaps are in
        ``latency_summary``.)"""
        rec = self._req.get(uid)
        if rec is None or k <= 0:
            return
        start = rec["last_ns"] if rec["last_ns"] is not None \
            else rec["t_admit"]
        per = (t_ns - start) / k
        i0 = 0
        if not rec["first_done"]:
            self._req_first_token(uid, int(start + per))
            i0 = 1
        self._lat["itl_ms"].extend([per / 1e6] * (k - i0))
        rec["last_ns"] = t_ns

    def _req_finish(self, uid: int, **args) -> None:
        rec = self._req_close(uid)
        if rec is None:
            return
        self._lat["e2e_ms"].append(
            (time.monotonic_ns() - rec["t_admit"]) / 1e6)
        rec["span"].end(**args)

    def _req_drop(self, uid: int) -> None:
        """Admission rolled back — close the spans without latency samples
        (a cancelled request is not an SLO data point). Deliberately
        TOLERANT of an absent record: with tracing off no record was ever
        opened, and the rollback paths call this unconditionally. The
        error-bearing surface for unknown/already-finished uids is
        ``finish()``/``park()``/``fork()`` via ``StateManager.lookup``
        (one consistent :class:`UnknownSequenceError`)."""
        rec = self._req_close(uid)
        if rec is not None:
            rec["span"].end(cancelled=True)

    # ------------------------------------------------------------------ #
    def _refuse_features(self) -> None:
        """Configuration-time refusals (``_REFUSALS``): the first feature
        that is on and that a kind of the family's cache state refuses."""
        cfg = self.config
        kq = getattr(cfg, "kv_quant", None)
        on = {"inference.prefix_cache": cfg.prefix_cache.enabled,
              "inference.prefix_cache.host_spill":
                  getattr(cfg.prefix_cache, "host_spill", False),
              "inference.speculative": cfg.speculative.enabled,
              "inference.kv_quant": kq is not None and kq.enabled,
              "inference.tensor_parallel": cfg.tensor_parallel.tp_size > 1}
        for error, refused in self._refusals:
            for feature, why in refused.items():
                if on.get(feature):
                    raise error(feature, why)

    def _refuse_call(self, call: str, column: str) -> None:
        """Call-time refusal of ``call``, the table's ``column``."""
        for error, refused in self._refusals:
            if column in refused:
                raise error(call, refused[column])

    def _kv_kind_args(self, firsts, counts, prefix: str = "") -> Dict[str, int]:
        """Span arguments of a call in a family with window layers: the
        cached tokens ONE layer of each kind reads - a full layer every
        token up to each sequence's last row, a window layer what lies
        inside its first row's window (``kv_tokens_full``,
        ``kv_tokens_window``; one sequence a pair of ``firsts``, its first
        row's position, and ``counts``, its rows). In a family with a latent
        cache: ``kv_tokens_latent``, the cached rows ONE layer reads. None
        for any other family."""
        if not (self._window or self._latent):
            return {}
        firsts = np.asarray(firsts, np.int64)
        ends = firsts + np.asarray(counts, np.int64)
        if self._latent:
            return {prefix + "kv_tokens_latent": int(ends.sum())}
        out = {prefix + "kv_tokens_full": int(ends.sum())}
        for window in self._window.values():
            key = prefix + "kv_tokens_window"
            out[key] = out.get(key, 0) + int(
                (ends - np.maximum(firsts - window + 1, 0)).sum())
        return out

    def _past_window_args(self, live) -> Dict[str, int]:
        """``rows_past_window`` of a ``decode_step`` in a family with window
        layers: the decode rows whose context is longer than the window -
        the rows for which a window layer reads less than a full layer, so
        whether the call really mixes the two regimes."""
        if not self._window:
            return {}
        window = min(self._window.values())
        return {"rows_past_window":
                sum(d.seen_tokens + 1 > window for d in live)}

    def _table(self, desc, n: int) -> np.ndarray:
        """``desc``'s block table for a call that writes its next ``n``
        tokens: every kind covers them first (a prompt's blocks of the full
        kind are claimed at admission; a window kind's as the calls reach
        them, those behind the window given back)."""
        self.state.extend(desc, n)
        return self.state.block_table(desc)

    def _sparse_args(self, contexts, prefix: str = "") -> Dict[str, int]:
        """Span arguments of a call's learned selection, ONE layer's: the
        rows at ``contexts`` (each row's own position + 1), the cached
        tokens they score and the tokens attention reads
        (``sparse_ctx_scored``, ``sparse_kv_selected``; the chunk's ride as
        ``chunk_sparse_*``), counted into ``sparse_stats`` too. None for a
        family without one."""
        if not self._indexed:
            return {}
        args = self.family.sparse_rows(self.family.cfg, contexts)
        for name, n in args.items():
            self.sparse_stats[name[len("sparse_"):]] += n
        return {prefix + name: n for name, n in args.items()
                if name != "sparse_rows"}

    def _chunk_contexts(self, ch: _Chunk):
        return ch.ctx + 1 + np.arange(len(ch.tokens))

    def _jit(self, key, fn, **jit_kwargs):
        """Every paged program routes through the compile monitor's shared
        registration helper. ``key[0]`` is the program FAMILY name, so a new
        bucket/shape of an existing family registers as a recompile — which
        is exactly what an unbucketed-prompt recompilation storm looks like.
        Default OFF → the exact ``jax.jit`` object back. The monitor is told
        the KV pools, so each compile says what it copies of them
        (``pool_copy_bytes``: 0 for the forward programs, whose layers write
        the pools where they lie) and what it aliases (``aliased_bytes``)."""
        return self.compile_monitor.jit(
            str(key[0]), fn, group="Serving",
            pools=[c.shape for c in jax.tree.leaves(self.cache)],
            **jit_kwargs)

    # ------------------------------------------------------------------ #
    # the programs: ONE forward (``_paged_forward``) that scores the rows
    # the program reads, one of two samplers (``_sampler``), in four
    # builders - prefill, decode, decode_chunk (a prompt chunk, with a
    # step's decodes beside it in the one forward), spec_verify.
    # ``_dispatch`` launches all of them.
    # ------------------------------------------------------------------ #
    def _paged_forward(self, params, tokens, cache, tables, ctx, valid,
                       slots=None, rows=None):
        """The engine's ONE call of the family's paged forward, traced inside
        every program: ``tokens`` [b, t] at context offsets ``ctx`` [b]
        through block tables [b, blocks], ``params`` as ``_dq`` hands them
        over; rows where ``valid`` [b, t] is False write nothing. A mixed
        call: ``tables`` is a ``MixedCall``, ``ctx`` None and ``tokens``
        [1, slots + chunk] (``_decode_chunk_fn``). ``slots``
        [b]: each row's sequence slot, for a family with recurrent state and
        a call whose rows are not the slots in order (the prefills; a
        decode-shaped call's row i IS slot i, the family's default).
        ``rows`` [b, r]: the rows along ``t`` whose logits the program
        reads (a prefill's last real rows; a mixed call's decode rows and
        its chunk's last real row) - the family's head runs on those alone;
        None (``decode``, ``verify``) reads them all.
        Returns (logits [b, t, V] fp32 - [b, r, V] with ``rows`` -, cache)."""
        kw = {} if slots is None else {"slots": slots}
        return self._apply_paged(self.family.cfg, params, tokens, cache,
                                 tables, ctx, valid=valid, rows=rows, **kw)

    def _prefill_fn(self, pad_t: int, n: int, with_ctx: bool, rows: bool):
        """One compiled prefill over ``n`` admitted sequences at once —
        admission bursts (serving start, high churn) run one program call
        instead of n (the reference schedules multi-sequence ragged prefill
        batches the same way). Callers pad n to a power-of-two bucket with
        zero-length dummy rows (masked by ``valid``, writing nothing) so
        compile count stays O(log max_sequences) per pad_t, not
        O(max_sequences). Per-row rng keys fold in each uid, keeping
        first-token sampling independent of burst composition.

        ``with_ctx``: the prefix-cache admission path — row i's tokens are
        the UNCACHED suffix of its prompt and ``ctx[i]`` counts the tokens
        already resolved to shared blocks, so positions/attention pick up
        mid-prompt exactly like a split-prefill chunk does. Compiled only
        when the cache is enabled AND a batch actually hit; without it the
        offset is a zeros constant of the program, so cache-off admissions
        keep the zero-offset programs byte for byte.

        ``rows``: see ``_sampler`` (the static variant would also break
        admission bursts into per-config groups)."""
        name = "prefill" + ("_ctx" if with_ctx else "") \
            + ("_dyn" if rows else "")
        key = (name, pad_t, n)
        if key not in self._paged_fns:
            pick = _sampler(rows)

            def prefill(params, cache, tokens, lengths, tables, *rest):
                # tokens [n, pad_t]; lengths [n]; tables [n, blocks]; then
                # ctx [n] (with_ctx), slots [n] (recurrent state), rng,
                # uids [n], sampling arrays (rows)
                rest = list(rest)
                ctx = rest.pop(0) if with_ctx else None
                slots = rest.pop(0) if self._recurrent else None
                rng, uids, *sp_rows = rest
                valid = jnp.arange(pad_t)[None, :] < lengths[:, None]
                dq = self._dq(params)
                if not with_ctx:   # numpy: a constant the ops can read
                    ctx = np.zeros((n,), np.int32)
                logits, cache = self._paged_forward(
                    dq, tokens, cache, tables, ctx, valid, slots,
                    rows=_last_real(lengths)[:, None])
                keys = jax.vmap(lambda u: jax.random.fold_in(rng, u))(uids)
                toks = jax.vmap(pick)(keys, logits[:, 0], *sp_rows)
                return toks.astype(jnp.int32), cache

            self._paged_fns[key] = self._jit(key, prefill, donate_argnums=(1,))
        return self._paged_fns[key]

    _sp_warned = False

    def _warn_ignored_sp(self, sp: SamplingParams) -> None:
        """step()/step_many() sample with ADMISSION-time params; a caller
        passing a non-default sp here (the pre-r4 API contract) would
        otherwise silently get each slot's put()-time config instead."""
        if not self._sp_warned and self._canon_sp(sp) != _GREEDY:
            import warnings

            warnings.warn(
                "step()/step_many() ignore their sp argument — sampling "
                "params are per-request, fixed at put()/put_split() time; "
                "pass them there instead", DeprecationWarning, stacklevel=3)
            self._sp_warned = True

    @staticmethod
    def _canon_sp(sp: SamplingParams) -> SamplingParams:
        """Greedy-equivalent configs (greedy=True, or temperature 0) all
        canonicalize to ONE params value so they share compiled programs."""
        if sp.greedy or sp.temperature == 0.0:
            return _GREEDY
        return sp

    def _copy_block_fn(self):
        """One compiled (src, dst are traced scalars) whole-block copy in the
        KV pool — the device half of copy-on-write: before a sequence appends
        into a block it shares, the host allocator hands it a private block
        and this stamps the shared block's contents into it."""
        key = ("copy_block",)
        if key not in self._paged_fns:

            def cp(cache, src, dst):
                return self._map_block_leaves(
                    lambda c: c.at[:, dst].set(c[:, src]), cache)

            self._paged_fns[key] = self._jit(key, cp, donate_argnums=(0,))
        return self._paged_fns[key]

    def _map_block_leaves(self, fn, cache):
        """``fn`` over the cache leaves that have the block axis (all of
        them but a recurrent family's per-slot state leaves, which pass
        through as they are)."""
        state = self.family.state_leaves
        if not state:
            return jax.tree.map(fn, cache)
        return {name: leaf if name in state else jax.tree.map(fn, leaf)
                for name, leaf in cache.items()}

    def _spill_read_block(self, b: int):
        """One block's per-cache-leaf contents as PRIVATE device slices —
        the eviction path hands these to the HostKVPool, whose transfer
        worker materializes the host copies asynchronously (the slice is a
        fresh buffer, so the source block may be reclaimed and rewritten
        immediately)."""
        return [leaf[:, b] for leaf in jax.tree.leaves(self.cache)]

    def _spill_write_fn(self):
        """One compiled whole-block write into the KV pool — the device
        half of a host-spill restore (dst is a traced scalar; one compile
        total, like ``_copy_block_fn``)."""
        key = ("spill_write",)
        if key not in self._paged_fns:

            def wr(cache, dst, data):
                leaves, tdef = jax.tree_util.tree_flatten(cache)
                new = [c.at[:, dst].set(d.astype(c.dtype))
                       for c, d in zip(leaves, data)]
                return jax.tree_util.tree_unflatten(tdef, new)

            self._paged_fns[key] = self._jit(key, wr, donate_argnums=(0,))
        return self._paged_fns[key]

    def _spill_write_block(self, b: int, data) -> None:
        """Stamp spilled host contents into freshly allocated block ``b``
        before the admission that restored it dispatches."""
        fn = self._spill_write_fn()
        leaves = jax.tree.leaves(self.cache)
        dev = [jnp.asarray(d) for d, _ in zip(data, leaves)]
        self.cache = fn(self.cache, jnp.asarray(b, jnp.int32), dev)

    def _copy_blocks(self, pairs) -> None:
        """Apply the (src, dst) copies ``StateManager.ensure_writable``
        scheduled, before the step that writes into dst launches."""
        if not pairs:
            return
        fn = self._copy_block_fn()
        for src, dst in pairs:
            self.cache = fn(self.cache, jnp.asarray(src, jnp.int32),
                            jnp.asarray(dst, jnp.int32))

    def _dispatch(self, fn, pre, seed: int, post=(), prev: bool = False):
        """The one place a forward program is launched: ``pre`` and ``post``
        are the call's host arrays in the program's argument order, on
        either side of its rng key, each uploaded here and nowhere else.
        ``prev``: the program resolves its slots' tokens from the newest
        launched result (``_own_tokens``), which goes in after the cache as
        the device array it is - never read, never uploaded. Returns what
        the program returns as a tuple - the donated cache last, for the
        caller to take back."""
        with self.tracer.span("engine_dispatch", cat="serving"):
            out = fn(self.params, self.cache,
                     *((self._prev,) if prev else ()),
                     *map(jnp.asarray, pre),
                     jax.random.PRNGKey(seed), *map(jnp.asarray, post))
        return out if isinstance(out, tuple) else (out,)

    def _kv_blocks(self, kv_tokens: int) -> int:
        """Blocks a prefill call's longest row attends over (cached context
        + this call's tokens): what the flash kernel walks of the table's
        ``max_blocks_per_seq``."""
        return -(-kv_tokens // self.state.block_size)

    def _row_args(self, rows: int, read: int) -> Dict[str, int]:
        """Span arguments of a launch over ``rows`` token rows (padding
        included) whose program reads ``read`` rows' logits: ``rows`` and
        ``head_rows``, the rows its head scores. Counted as they are said
        (``engine_events``)."""
        self.rows += rows
        self.head_rows += read
        return {"rows": rows, "head_rows": read}

    def _moe_args(self, rows: int) -> Dict[str, int]:
        """Span arguments of a call over ``rows`` token rows (padding
        included) in a MoE family: ``moe_rows_routed`` and
        ``moe_rows_computed`` of ONE MoE layer (the call runs ``num_layers``
        of them). Shape facts, known at dispatch; none for a dense family."""
        fn = self.family.moe_rows
        return fn(self.family.cfg, rows) if fn else {}

    def _ssm_args(self, rows: int, tokens: int,
                  admitted: bool = False) -> Dict[str, int]:
        """Span arguments of a call in a family with recurrent state:
        ``ssm_rows``, the rows whose state ONE state-space layer of the call
        advances (a decode's active slots, a prefill's admitted sequences),
        and ``ssm_tokens``, the tokens it advances them by. ``last_step``
        counts them too (a one-shot prefill ``admitted`` between steps with
        the next step, as its ``prefill_kv_tokens``). None for any other
        family."""
        if not self._recurrent:
            return {}
        into = self._admitted_ssm if admitted else self.last_step
        for key, n in (("ssm_rows", rows), ("ssm_tokens", tokens)):
            into[key] = into.get(key, 0) + n
        return {"ssm_rows": rows, "ssm_tokens": tokens}

    def _state_args(self, rows: int, chunk_rows: int) -> Dict[str, int]:
        """Span arguments of a step's launch in a family that names its
        recurrent layer's rows itself (``ModelFamily.state_rows``): the live
        single-token rows and the rows of the chunk riding with them, of ONE
        layer of the call. None for any other family."""
        fn = self.family.state_rows
        return fn(self.family.cfg, rows, chunk_rows) if fn else {}

    def _walked_pool(self):
        """The pool whose shape says the paged walks' tile sizes: the K pool,
        or a latent cache's one pool; None for a family that has neither."""
        return self.cache.get("k", self.cache.get("latent"))

    def _attn_tile_args(self, live) -> Dict[str, float]:
        """Span arguments of a decode dispatch over the slots as they stand:
        of ONE layer's ``paged_decode`` call (``paged_sparse_decode``, the
        same walk, under a learned selection), the KV tiles that hold live
        context, the tiles the walk takes - the same tiles where it fetches
        its own pages, every slot as far as the longest where it is a grid
        of ``BlockSpec`` pages - and their ratio (the kernel's own tile
        sizes: ``ops/pallas/paged_attention.py``). None for a family whose
        paged cache is not ``init_paged_pools``'. A learned selection's
        ``paged_index_scores`` call over the slots of ``live``, the
        sequences that decode, says the same of its own tiles as
        ``index_tiles_live`` / ``index_tiles_grid`` /
        ``index_live_tile_share`` (``paged_sparse_attention.py
        index_tile_counts``; no share where nothing decodes and the call
        takes no tile)."""
        from ..ops.pallas.paged_attention import decode_tile_counts

        pool = self._walked_pool()
        if pool is None:
            return {}
        width = self.state.max_blocks_per_seq
        n_live, grid = decode_tile_counts(
            self._slot_lens, self.family.cfg.num_heads, pool.shape,
            pool.dtype.itemsize, width, "k_scale" in self.cache,
            1 if self._latent else 2)
        out = {"attn_tiles_live": n_live, "attn_tiles_grid": grid,
               "attn_live_tile_share": n_live / grid}
        if self._indexed:
            from ..ops.pallas.paged_sparse_attention import index_tile_counts

            decoding = np.zeros(self._slot_lens.shape, np.int32)
            decoding[[d.slot for d in live]] = 1
            n_live, grid = index_tile_counts(
                self._slot_lens, decoding, self.cache["kI"].shape,
                self.state.block_size, width)
            out.update(index_tiles_live=n_live, index_tiles_grid=grid)
            if grid:
                out["index_live_tile_share"] = n_live / grid
        return out

    def _chunk_tile_args(self, ch: _Chunk) -> Dict[str, int]:
        """Span arguments of a chunk's ``paged_prefill`` walk, host integers
        the call already holds: the KV tiles that hold context its rows
        attend, the tiles the walk takes (the same ones where it fetches its
        own pages) and the steps a grid as wide as the block table would
        take (``chunk_attn_tiles_live`` / ``_grid`` / ``_table``;
        ``ops/pallas/paged_attention.py prefill_tile_counts``) - of ONE
        layer's call; in a family with window kinds of one call a kind, each
        times the kind's layers, summed - and the KV tokens of a step of the
        walk, ``chunk_attn_kv_tile`` (the widest of the kinds'). Under a
        learned selection the walk is ``paged_sparse_prefill``, the same
        form at a tile of its own (``paged_sparse_attention.prefill_pages``).
        None for a family whose paged cache is not ``init_paged_pools``' (a
        latent pool is: one KV head, every query head in its group)."""
        from ..ops.pallas.paged_attention import (prefill_kv_pages,
                                                  prefill_tile_counts)

        pool = self._walked_pool()
        if pool is None:
            return {}
        if self._indexed:
            from ..ops.pallas.paged_sparse_attention import prefill_pages
        state = self.state
        walks = [(pool.shape, state.max_blocks_per_seq, 0, None)]
        walks += [(self.cache["k_" + kind.name].shape, kind.blocks_per_seq,
                   state.first_live(kind, ch.ctx) * state.block_size,
                   kind.window) for kind in state.window_kinds]
        how = (pool.dtype.itemsize, "k_scale" in self.cache,
               1 if self._latent else 2)
        total, tile = (0, 0, 0), 0
        for shape, width, given, window in walks:
            layers = shape[0] if len(walks) > 1 else 1
            call = ([ch.ctx - given], [len(ch.tokens)], ch.width,
                    self.family.cfg.num_heads, shape, width)
            pages = prefill_pages(*call[2:], how[0]) if self._indexed \
                else prefill_kv_pages(*call, *how)
            counts = prefill_tile_counts(*call, window, *how, pages=pages)
            total = tuple(a + layers * n for a, n in zip(total, counts))
            tile = max(tile, pages * shape[-2])
        return dict(zip(("chunk_attn_tiles_live", "chunk_attn_tiles_grid",
                         "chunk_attn_tiles_table"), total),
                    chunk_attn_kv_tile=tile)

    def _next_chunk(self) -> _Chunk:
        """The OLDEST pending split prefill's next chunk (FIFO, the
        reference scheduler's arrival order)."""
        uid = next(iter(self._pending_prefill))
        prompt, sp = self._pending_prefill[uid]
        desc = self.state.seqs[uid]
        width = _round_up(max(self.config.split_prefill_chunk, 1),
                          self.config.prefill_bucket)
        done = desc.seen_tokens
        tokens = prompt[done:done + width]
        return _Chunk(uid, desc, sp, tokens, done,
                      done + len(tokens) >= len(prompt), width)

    def _chunk_args(self, ch: _Chunk) -> Dict[str, Any]:
        """What a span says of the chunk its call runs."""
        return {"uid": ch.uid, "tokens": len(ch.tokens), "ctx": ch.ctx,
                "final": ch.final,
                "kv_blocks": self._kv_blocks(ch.ctx + len(ch.tokens))}

    def _chunk_landed(self, ch: _Chunk, table) -> None:
        """The bookkeeping of a chunk whose program has been dispatched, all
        of it lengths: a final chunk's sequence is seated for the NEXT
        decode-shaped call, and its first token lands when the host reads
        it (``_first_token``)."""
        n, desc = len(ch.tokens), ch.desc
        self.last_step["prefill_tokens"] += n
        self.last_step["prefill_kv_tokens"] += ch.ctx + n
        self.prefill_tokens_written += n
        desc.seen_tokens = ch.ctx + n
        self.state.mark_filled(desc)      # completed chunks are matchable
        if ch.final:
            del self._pending_prefill[ch.uid]
            desc.prefilling = False
            self._seat(desc, table, self._canon_sp(ch.sp))

    def _first_token(self, ch: _Chunk, tok: int) -> None:
        """The first token a final chunk sampled, read from the device."""
        desc = ch.desc
        desc.last_token = tok
        desc.generated.append(tok)
        self._slot_tokens[desc.slot] = tok
        self._out.setdefault(ch.uid, []).append(tok)

    def _chunk_program(self, ch: _Chunk, live, table):
        """(program, its arrays before the key, its arrays after) for one
        chunk: ``decode_chunk``, whose decode rows are the slots of
        ``live`` - none, where nothing decodes beside the chunk -, so that
        every chunk runs (and a server's start-up loads) one program."""
        chunk = ch.arrays(table, self._recurrent)
        uid = (np.int32(ch.uid),)
        # a chunk that does not end its prompt samples for nothing; slots
        # hold canonical params (``_canon_sp``): see ``_sampler``
        sp = self._canon_sp(ch.sp) if ch.final else _GREEDY
        rows = sp != _GREEDY or any(
            self._slot_sp[d.slot] != _GREEDY for d in live)
        return (self._decode_chunk_fn(ch.width, rows),
                self._slots(live) + chunk,
                uid + (sp_arrays(self._slot_sp + [sp]) if rows else ()))

    def _advance_prefill(self, seed: int = 0) -> bool:
        """Advance the oldest pending split prefill by one chunk with
        nothing decoding beside it (``decode_chunk`` with no slot active),
        sampling with the SamplingParams given at put_split time. Returns
        whether that chunk completed its prompt. Its first token is then in
        flight: the program is decode-shaped, and the next one takes the
        token from its result."""
        if not self._pending_prefill:
            return False
        ch = self._next_chunk()
        rec = self._req.get(ch.uid)     # the request's ring lifecycle
        slots = len(self._slot_tokens)
        rows = ch.width + slots
        seq = self._next_seq()
        with self.tracer.span(
                "prefill_chunk", cat="serving", seq=seq,
                trace=rec["trace"] if rec else None,
                parent=rec["span"].span_id if rec else None,
                table_blocks=self.state.max_blocks_per_seq,
                **self._chunk_args(ch), **self._moe_args(rows),
                **self._row_args(rows, slots + 1),
                **self._ssm_args(1, len(ch.tokens)),
                **self._state_args(0, len(ch.tokens)),
                **self._sparse_args(self._chunk_contexts(ch)),
                **self._kv_kind_args([ch.ctx], [len(ch.tokens)]),
                **self._chunk_tile_args(ch)):
            with self.tracer.span("engine_prep", cat="serving"):
                table = self._table(ch.desc, len(ch.tokens))
                fn, pre, post = self._chunk_program(ch, (), table)
            if self._trace_on:
                self._req_compute_begin(ch.uid)  # first chunk ends queue-wait
            tok, self.cache = self._dispatch(fn, pre, seed, post, prev=True)
            self._chunk_landed(ch, table)
            # no engine_wait: the call is asynchronous and nothing here
            # blocks on it (nor reads a token a chunk that does not end its
            # prompt sampled for nothing), so this span says dispatch, not
            # device
            if ch.final:
                self._launched(tok, (), ch, None, seq)
            return ch.final

    def _seat(self, desc, table, sp: SamplingParams) -> None:
        """The sequence's slot as the next decode-shaped call reads it."""
        s = desc.slot
        self._slot_tokens[s] = desc.last_token
        self._slot_src[s] = _FROM_HOST
        self._slot_lens[s] = desc.seen_tokens
        self._slot_tables[s] = table
        self._slot_sp[s] = sp

    def put_split(self, uid: int, prompt_tokens,
                  sp: SamplingParams = SamplingParams(greedy=True)) -> None:
        """Admit a sequence WITHOUT prefilling it: the prompt enters the KV
        cache one chunk per subsequent step()/step_many() call, alongside
        ongoing decodes — so a long prompt never blocks live sequences for
        more than one chunk's compute (the FastGen Dynamic-SplitFuse
        scheduling property). The first sampled token arrives in the step()
        result that completes the prompt.

        With the prefix cache enabled, a cached prefix is resolved to shared
        blocks at admission and chunking starts at the first uncached token —
        a mostly-cached long prompt may need only one chunk."""
        prompt = np.asarray(prompt_tokens, np.int32)
        desc, cached = self.state.admit_prompt(uid, prompt)
        self._req_admit(uid, len(prompt), split=True)
        desc.seen_tokens = cached   # chunk loop starts after the cached hit
        desc.prefilling = True
        self._pending_prefill[uid] = (prompt, sp)

    def _decode_fn(self, k: int, rows: bool):
        """Decode over every sequence slot, ``k`` ticks in ONE compiled
        program: ``k == 1`` is the single step, ``k > 1`` a ``lax.scan`` of
        the same tick with a single host sync at the end. The reference's
        persistent-kernel decode loop achieves the same thing on GPU; over a
        network-attached TPU the per-step host round-trip dominates
        single-step decode, so the scan is the serving fast path (block
        capacity is reserved for all k tokens before launch — see
        ``_reserve``). ``rows``: see ``_sampler``. The slots' tokens are
        resolved on the device from the result of the program launched
        before (``_own_tokens``), and the single step returns its tokens in
        that result's one shape, ``[slots + 1]`` (the last entry is a final
        chunk's first token in ``decode_chunk`` and nothing here), so the
        next program takes either's result under one signature."""
        name = ("decode" if k == 1 else "decode_many") \
            + ("_dyn" if rows else "")
        key = (name, k)
        if key not in self._paged_fns:
            pick = _sampler(rows)

            def decode(params, cache, prev, seat, lens, tables, active, rng,
                       *sp_rows):
                dq = self._dq(params)
                tokens = _own_tokens(prev, seat)

                def tick(tokens, lens, cache, key_t):
                    # inactive slots write nothing (valid=False)
                    logits, cache = self._paged_forward(
                        dq, tokens[:, None], cache, tables, lens,
                        active[:, None])
                    nxt = pick(key_t, logits[:, 0], *sp_rows)
                    return nxt.astype(jnp.int32), cache

                if k == 1:
                    nxt, cache = tick(tokens, lens, cache, rng)
                    return jnp.pad(nxt, (0, 1)), cache

                def body(carry, key_t):
                    tokens, lens, cache = carry
                    nxt, cache = tick(tokens, lens, cache, key_t)
                    return (nxt, lens + active.astype(jnp.int32), cache), nxt

                keys = jax.random.split(rng, k)
                (tokens, lens, cache), toks = jax.lax.scan(
                    body, (tokens, lens, cache), keys)
                return toks, lens, cache  # toks: [k, B]

            # the programs' names are read off the device trace
            # (benchmark/readers: jit_decode)
            decode.__name__ = "decode" if k == 1 else "decode_many"
            self._paged_fns[key] = self._jit(key, decode, donate_argnums=(1,))
        return self._paged_fns[key]

    def _decode_chunk_fn(self, chunk_t: int, rows: bool):
        """A prefill chunk AND the step's decodes in ONE forward (a mixed
        call: ``models/_paged.py MixedCall``): every slot's token and the
        chunk's ``chunk_t`` tokens are one row dimension of ``slots +
        chunk_t`` rows, so the tick reads every weight once; only attention
        (and a recurrent state) runs a segment at a time. ONE variant for
        mid and final chunks: the chunk's last real row is always sampled
        (its key folds in the uid) and the host drops the token of a chunk
        that does not end its prompt - so the program compiles once a
        ``(slots, chunk_t)``, greedy or ``rows`` (see ``_sampler``; the
        per-row arrays are the slots' and then the chunk's). The slots'
        tokens come as ``decode``'s do. Returns (tokens [slots + 1],
        cache)."""
        name = "decode_chunk" + ("_dyn" if rows else "")
        key = (name, chunk_t)
        if key not in self._paged_fns:
            pick = _sampler(rows)

            def decode_chunk(params, cache, prev, seat, lens, tables, active,
                             chunk, n_valid, ctx, table, *rest):
                # the slots as ``decode`` takes them; the chunk [1,
                # chunk_t], its real tokens, context offset and block
                # table, then the sequence's slot (recurrent state), rng,
                # uid; then the sampling arrays [slots + 1] (rows)
                rest = list(rest)
                slot = rest.pop(0) if self._recurrent else None
                rng, uid, *sp_rows = rest
                tokens = _own_tokens(prev, seat)
                b = tokens.shape[0]
                call = MixedCall(tables, lens, active, table, ctx, n_valid,
                                 slot)
                # the rows the program reads: every slot's decode row and
                # the chunk's last real one, ``slots + 1`` of ``slots +
                # chunk_t``
                rows = jnp.concatenate(
                    [jnp.arange(b), b + _last_real(n_valid)[None]])[None]
                logits, cache = self._paged_forward(
                    self._dq(params),
                    jnp.concatenate([tokens, chunk[0]])[None], cache, call,
                    None, call.valid(b + chunk_t), rows=rows)
                nxt = pick(rng, logits[0, :b], *(a[:b] for a in sp_rows))
                first = pick(jax.random.fold_in(rng, uid), logits[0, b],
                             *(a[b] for a in sp_rows))
                return jnp.concatenate([nxt, first[None]]).astype(
                    jnp.int32), cache

            # the name is read off the device trace, as ``decode``'s
            # (benchmark/readers: ^jit_decode finds the tick's program)
            self._paged_fns[key] = self._jit(key, decode_chunk,
                                             donate_argnums=(1,))
        return self._paged_fns[key]

    # ------------------------------------------------------------------ #
    # speculative decoding: prompt-lookup drafting + batched verification +
    # KV rollback (docs/serving.md)
    # ------------------------------------------------------------------ #
    def _verify_fn(self, kp1: int):
        """ONE compiled forward pass scoring all ``kp1 - 1`` draft positions
        of every sequence slot against the paged cache — the ctx-offset
        prefill machinery applied at decode time: row i feeds
        ``[last_token, draft_1..draft_k]`` at context offset ``lens[i]`` with
        positions past ``1 + draft_len[i]`` masked (they write nothing). Every
        layer's attention is the ``paged_prefill`` kernel over the block
        table, as for any multi-token call (dequant-in-register in kv_quant
        mode).

        Acceptance (``sampling.accept_drafts``, this program's sampler) runs
        on-device so the step has exactly one host sync. Returns
        (accepted_len [B], next_token [B], cache)."""
        key = ("spec_verify", kp1)
        if key not in self._paged_fns:

            def verify(params, cache, tokens, lens, tables, active, nvalid,
                       drafts, rng, uids, temp, topk, topp, greedy):
                # tokens [B, kp1]; nvalid [B] = 1 + draft_len;
                # drafts [B, kp1-1] (zero-padded past draft_len)
                valid = (jnp.arange(kp1)[None, :] < nvalid[:, None]) \
                    & active[:, None]
                logits, cache = self._paged_forward(
                    self._dq(params), tokens, cache, tables, lens, valid)
                m, nxt = accept_drafts(rng, logits, drafts, nvalid, uids, temp,
                                       topk, topp, greedy)
                return m, nxt.astype(jnp.int32), cache

            self._paged_fns[key] = self._jit(key, verify, donate_argnums=(1,))
        return self._paged_fns[key]

    def _draft_tokens(self, desc) -> List[int]:
        """Prompt-lookup draft for one live sequence, clamped so the verify
        write window ``[seen, seen + len + 1)`` stays inside max_seq_len and
        the fixed-width block table."""
        room = min(self.family.cfg.max_seq_len,
                   self.state.max_blocks_per_seq * self.state.block_size) \
            - desc.seen_tokens - 1
        k = min(self._spec_k, room)
        if k <= 0:
            return []
        return prompt_lookup_draft(desc.tokens + [desc.last_token], k,
                                   self._spec_ngram_max,
                                   self._spec_min_match)

    def _spec_step(self, live, seed: int = 0) -> bool:
        """One speculative decode step over ``live``: draft, verify every
        draft position in one batched forward pass, accept the longest
        agreeing prefix per sequence, roll back rejected KV. Each sequence
        emits at least one token (the correction/bonus sample), up to
        ``max_draft_tokens + 1``, for the next ``collect``. Returns False
        when no sequence produced a draft (the caller runs the plain decode
        program, keeping draft-less steps bit-identical to non-spec
        serving). Drafts read history, so no token of ``live`` may be in
        flight (a prompt's first token may: its sequence is not live yet)."""
        drafts = {d.uid: self._draft_tokens(d) for d in live}
        bs = self.state.block_size
        # capacity guard: verification may need blocks for up to k+1 new
        # positions per sequence; if the pool (free + evictable) cannot
        # cover the batch, drop the drafts — a plain decode step needs the
        # fewest blocks and matches non-spec admission behavior
        need = 0
        for d in live:
            want = d.seen_tokens + len(drafts[d.uid]) + 1
            need += max(0, (want + bs - 1) // bs - len(d.blocks))
        if need > self.state.allocator.free_blocks + \
                self.state.retained_blocks:
            drafts = {u: [] for u in drafts}
        if not any(drafts.values()):
            return False
        kmax = self._spec_k
        self.spec_stats["verify_steps"] += 1
        self.spec_stats["step_seqs"] += len(live)
        out: Dict[int, List[int]] = {}
        st = self.spec_stats
        seq = self._next_seq()
        with self.tracer.span(
                "spec_verify", cat="serving", seq=seq, batch=len(live),
                drafted=sum(len(v) for v in drafts.values())) as span:
            with self.tracer.span("engine_prep", cat="serving"):
                self._reserve(live, (len(drafts[d.uid]) + 1 for d in live))
                B = self._slot_tokens.shape[0]
                tok_w = np.zeros((B, kmax + 1), np.int32)
                tok_w[:, 0] = self._slot_tokens
                dr_arr = np.zeros((B, kmax), np.int32)
                nvalid = np.ones((B,), np.int32)
                uids_arr = np.zeros((B,), np.int32)
                for d in live:
                    dr = drafts[d.uid]
                    dr_arr[d.slot, :len(dr)] = dr
                    tok_w[d.slot, 1:len(dr) + 1] = dr
                    nvalid[d.slot] = 1 + len(dr)
                    uids_arr[d.slot] = d.uid
                fn = self._verify_fn(kmax + 1)
                sp_rows = sp_arrays(self._slot_sp)
            m, nxt, self.cache = self._dispatch(
                fn, self._slots(live, tok_w) + (nvalid, dr_arr), seed,
                (uids_arr,) + sp_rows)
            with self.tracer.span("engine_wait", cat="serving", seq=seq):
                m, nxt = np.asarray(m), np.asarray(nxt)
            t1 = time.monotonic_ns() if self._trace_on else 0
            with self.tracer.span("engine_emit", cat="serving"):
                kv = 0
                for d in live:
                    dr = drafts[d.uid]
                    dl = len(dr)
                    mi = min(int(m[d.slot]), dl)
                    tok = int(nxt[d.slot])
                    # KV positions seen..seen+dl now hold [last_token] +
                    # drafts; record them, then un-fill the rejected suffix
                    d.tokens.extend([d.last_token] + dr)
                    d.seen_tokens += dl + 1
                    kv += d.seen_tokens
                    if mi < dl:
                        pairs = self.state.truncate(
                            d, d.seen_tokens - (dl - mi))
                        self._copy_blocks(pairs)
                        self._slot_tables[d.slot] = self.state.block_table(d)
                    # what was written is recorded above, ahead of the
                    # rollback: the commit indexes only blocks that stand
                    out[d.uid] = emitted = dr[:mi] + [tok]
                    self._commit(d, (), emitted, t1)
                    st["drafted_tokens"] += dl
                    st["accepted_tokens"] += mi
                    st["emitted_tokens"] += mi + 1
                    st["rolled_back_tokens"] += dl - mi
                    st["verify_positions"] += dl + 1
                    st["verify_capacity"] += kmax + 1
            self.last_step.update(decode_seqs=len(live), kv_tokens=kv)
            span.set(kv_tokens=kv,
                     accepted=sum(len(v) - 1 for v in out.values()))
        return True

    # ------------------------------------------------------------------ #
    def put(self, uid: int, prompt_tokens, sp: SamplingParams = SamplingParams(greedy=True),
            seed: int = 0) -> int:
        """Admit one sequence and run its prefill; returns the first sampled
        token (reference ``engine_v2.put`` returns logits for the client to
        sample — here sampling is fused into the step)."""
        return self.put_many([(uid, prompt_tokens)], sp, seed=seed)[uid]

    def put_many(self, uid_prompts,
                 sp: SamplingParams = SamplingParams(greedy=True),
                 seed: int = 0) -> Dict[int, int]:
        """Admit a BATCH of sequences with one compiled prefill call →
        {uid: first sampled token}. Prompts pad to the longest one's bucket
        (same budget trade the reference's ragged prefill batches make).
        All-or-nothing: if capacity runs out mid-batch, already-admitted
        entries are retired before the error propagates (no half-admitted
        descriptors ever become visible to step())."""
        entries = []
        cached = []
        try:
            for uid, p in uid_prompts:
                prompt = np.asarray(p, np.int32)
                desc, hit = self.state.admit_prompt(uid, prompt)
                entries.append((uid, prompt, desc))
                cached.append(hit)
                self._req_admit(uid, len(prompt))
        except Exception:
            for uid, _, _ in entries:
                self.state.retire(uid)
                self._req_drop(uid)
            raise
        return self._prefill_admitted(entries, [sp] * len(entries), seed,
                                      cached=cached)

    def _prefill_admitted(self, entries, sps, seed: int,
                          cached) -> Dict[int, int]:
        """Batched prefill over already-admitted ``(uid, prompt, desc)``
        entries (callers admit first so capacity accounting stays exact),
        with per-ENTRY sampling params ``sps``. The batch pads to a
        power-of-two row count with masked dummy rows — one compile per
        (pad_t, bucket), not per burst size; an all-greedy burst runs the
        static argmax program, any stochastic entry switches to the
        per-row-array variant (one compile for every config mix).

        ``cached[i]`` tokens of entry i were resolved to shared blocks by the
        prefix cache: the forward pass then runs only over each prompt's
        uncached SUFFIX at its context offset. A batch with no hits (or with
        the cache off) takes the original zero-offset programs unchanged."""
        if not entries:
            return {}
        self.drain("put")  # the slots move: no program in flight across it
        seq = self._next_seq()
        sps = [self._canon_sp(s_) for s_ in sps]
        n = len(entries)
        n_pad = 1 << (n - 1).bit_length()
        pad_t = _round_up(max(max(len(p) - c for (_, p, _), c
                                  in zip(entries, cached)), 1),
                          self.config.prefill_bucket)
        out: Dict[int, int] = {}
        kv_rows = [len(p) for _, p, _ in entries]  # cached prefix + suffix
        with self.tracer.span("prefill_batch", cat="serving", seq=seq, n=n,
                              pad_t=pad_t,
                              kv_blocks=self._kv_blocks(max(kv_rows)),
                              table_blocks=self.state.max_blocks_per_seq,
                              **self._moe_args(n_pad * pad_t),
                              **self._row_args(n_pad * pad_t, n_pad),
                              **self._ssm_args(
                                  n, sum(kv_rows) - sum(cached), True)):
            with self.tracer.span("engine_prep", cat="serving"):
                padded = np.zeros((n_pad, pad_t), np.int32)
                lengths = np.zeros((n_pad,), np.int32)  # dummy rows: length 0
                ctx = np.zeros((n_pad,), np.int32)
                uids_arr = np.zeros((n_pad,), np.int32)
                tables = np.zeros((n_pad, self._slot_tables.shape[1]),
                                  np.int32)
                slots = np.zeros((n_pad,), np.int32)  # dummy rows write none
                for i, (uid, prompt, desc) in enumerate(entries):
                    slots[i] = desc.slot
                    suffix = prompt[cached[i]:]
                    padded[i, :len(suffix)] = suffix
                    lengths[i] = len(suffix)
                    ctx[i] = cached[i]
                    uids_arr[i] = uid
                    tables[i] = self._table(desc, len(suffix))
                with_ctx = any(cached)
                rows = not all(s_ == _GREEDY for s_ in sps)
                fn = self._prefill_fn(pad_t, n_pad, with_ctx, rows)
                # dummy rows sample greedily
                sp_rows = sp_arrays(sps + [_GREEDY] * (n_pad - n)) \
                    if rows else ()
            if self._trace_on:
                for uid, prompt, _ in entries:
                    self._req_admit(uid, len(prompt))  # generate() admits direct
                    self._req_compute_begin(uid)
                t0 = time.monotonic_ns()
            toks, self.cache = self._dispatch(
                fn, (padded, lengths, tables) + ((ctx,) if with_ctx else ())
                + ((slots,) if self._recurrent else ()),
                seed, (uids_arr,) + sp_rows)
            with self.tracer.span("engine_wait", cat="serving", seq=seq):
                toks = np.asarray(toks)
            t1 = time.monotonic_ns() if self._trace_on else 0
            self.prefill_tokens_written += int(lengths.sum())
            self._admitted_kv_tokens += sum(kv_rows)
            with self.tracer.span("engine_emit", cat="serving"):
                for i, (uid, prompt, desc) in enumerate(entries):
                    out[uid] = tok = int(toks[i])
                    desc.seen_tokens = len(prompt)
                    self.state.mark_filled(desc)  # full blocks → matchable
                    desc.last_token = tok
                    desc.generated.append(tok)
                    self._seat(desc, tables[i], sps[i])
                    if self._trace_on:
                        rec = self._req.get(uid)
                        if rec is not None:
                            # one interval credited to every request of the
                            # batch: explicit endpoints, ring only
                            self.tracer.complete(
                                "prefill", t0, t1, cat="serving",
                                trace=rec["trace"],
                                parent=rec["span"].span_id, uid=uid,
                                tokens=int(lengths[i]), cached=int(ctx[i]))
                        self._req_first_token(uid, t1)
        return out

    # ------------------------------------------------------------------ #
    # what launch(), step_many() and _spec_step() share around their program
    # ------------------------------------------------------------------ #
    def _prefill_then_live(self, seed: int, ride: bool = False, hold=()):
        """How a step begins: ``last_step`` starts over - the one-shot
        prefills that ran since the previous step (``put``/``put_many``, a
        scheduler tick's admissions) count with this step's
        ``prefill_kv_tokens`` -, the sequences to decode are listed (a
        prefilling one is not among them, the one this step's chunk
        completes included: it has its first token only; nor one of
        ``hold``, the uids the caller knows to be at their end), and the
        oldest split prefill advances one chunk. ``ride``: the caller runs
        a chunk and its decodes as ONE launch (``_launch_decode``; not a
        fused quantum, nor under speculation); where there are both, the
        chunk is left to it. Returns (live, the chunk left over or None)."""
        self.last_step = dict(_NO_WORK,
                              prefill_kv_tokens=self._admitted_kv_tokens,
                              **self._admitted_ssm)
        self._admitted_kv_tokens = 0
        self._admitted_ssm = {}
        live = [d for d in self.state.seqs.values()
                if not d.finished and not d.prefilling and d.uid not in hold]
        if ride and live and self._pending_prefill:
            return live, self._next_chunk()
        done = self._advance_prefill(seed)
        if not live:
            # no decodes in flight: the one-chunk-per-step bound exists to
            # protect live decodes from prefill stalls — with none to
            # protect, advance the oldest split prefill chunk after chunk
            # until it completes (it holds KV blocks the whole time), then
            # stop: the completed sequence is a live decode to protect again
            while self._pending_prefill and not done:
                done = self._advance_prefill(seed)
        return live, None

    def _reserve(self, live, counts) -> None:
        """Room for the tokens a decode-shaped call is about to write:
        ``counts`` gives, for each sequence of ``live``, how many (1 a step,
        k a quantum - all k up front, so the scan never needs the host
        mid-flight -, draft_len + 1 a verify window). Copy-on-write BEFORE
        extend: only pre-existing blocks can be shared; the blocks extend
        allocates are fresh (refcount 1). The copies are stamped here, before
        the program that writes into them launches."""
        cow = []
        for d, n in zip(live, counts):
            cow += self.state.ensure_writable(d, d.seen_tokens + n)
            self.state.extend(d, n)
            self._slot_tables[d.slot] = self.state.block_table(d)
        self._copy_blocks(cow)

    def _slots(self, live=(), tokens=None) -> Tuple:
        """(tokens, lens, tables, active) over every slot: what a
        decode-shaped program takes after the cache (and after the newest
        launched result, where it resolves its tokens from that:
        ``_dispatch``). Active are the slots of ``live``, the sequences the
        call decodes, and no other: a slot seated by this step's final
        chunk, or one whose sequence has finished and is not yet retired,
        holds a sequence but is not live, and a row computed for it would
        write its KV (and advance a recurrent state) a second time. The
        tokens are the seat row - the host's copy of a slot's last token, or
        the code that says where on the device it lies (``_own_tokens``) -
        unless ``tokens`` stands in (a verify window). Copies: a later
        launch moves the slot arrays while this program may still wait."""
        active = np.zeros(self._slot_lens.shape, bool)
        active[[d.slot for d in live]] = True
        if tokens is None:
            tokens = np.where(self._slot_src == _FROM_HOST,
                              self._slot_tokens, self._slot_src)
        return (tokens, self._slot_lens.copy(), self._slot_tables.copy(),
                active)

    def _commit(self, d, written, emitted, t_ns: int) -> int:
        """A synchronous decode-shaped call's tokens (a quantum's, a verify
        window's) land on sequence ``d``: ``written`` are the ids whose KV
        the call wrote after the context (recorded so the blocks they fill
        can be chain-hashed), ``emitted`` the tokens it produced, the last
        of them pending its write by the next call. Returns the sequence's
        KV length."""
        d.tokens.extend(written)
        d.seen_tokens += len(written)
        d.last_token = emitted[-1]
        d.generated.extend(emitted)
        self._slot_tokens[d.slot] = d.last_token
        self._slot_src[d.slot] = _FROM_HOST
        self._slot_lens[d.slot] = d.seen_tokens
        self.state.mark_filled(d)
        self._out.setdefault(d.uid, []).extend(emitted)
        if self._trace_on:
            self._req_tokens(d.uid, len(emitted), t_ns)
        return d.seen_tokens

    def _decode_quantum(self, k: int, live, seed: int, span,
                        seq: int) -> None:
        """``k`` decode ticks over ``live`` in one program, launch ``seq``,
        inside the caller's ``span``: reserve, dispatch, the ONE host sync,
        and each sequence's k tokens."""
        with self.tracer.span("engine_prep", cat="serving"):
            self._reserve(live, repeat(k))
            # slots hold canonical params (``_canon_sp``): see ``_sampler``
            rows = any(self._slot_sp[d.slot] != _GREEDY for d in live)
            fn = self._decode_fn(k, rows)
            sp_rows = sp_arrays(self._slot_sp) if rows else ()
        # (toks [k, slots], lens, cache); a quantum clamped to ONE tick (a
        # stream one token short of its context's end, ``step_many(1)``) is
        # the single step's program: (toks [slots + 1], cache)
        toks, *_, self.cache = self._dispatch(fn, self._slots(live), seed,
                                              sp_rows, prev=True)
        with self.tracer.span("engine_wait", cat="serving", seq=seq):
            toks = np.asarray(toks).reshape(k, -1)
        t1 = time.monotonic_ns() if self._trace_on else 0
        with self.tracer.span("engine_emit", cat="serving"):
            kv = 0
            for d in live:
                new = toks[:, d.slot].tolist()
                # KV writes of the call: the previous last_token, then each
                # sampled token except the newest (still pending its write)
                kv += self._commit(d, [d.last_token] + new[:-1], new, t1)
            self.last_step.update(decode_seqs=len(live), kv_tokens=kv)
            span.set(kv_tokens=kv)

    # ------------------------------------------------------------------ #
    # one program in flight: launch | collect (docs/serving.md)
    # ------------------------------------------------------------------ #
    def _launch_decode(self, live, seed: int,
                       ch: Optional[_Chunk] = None) -> None:
        """Launch one step's decodes over ``live`` - and, with ``ch``, its
        prefill chunk in the same program (``_decode_chunk_fn``) - under ONE
        ``decode_step`` span, and keep everything the launch can keep: every
        argument of the span is a length or a shape (``batch``,
        ``kv_tokens``, the tile counts and ``ssm_*`` are the decode rows',
        the MoE rows the whole call's - one pass through the expert bank, so
        no other span may carry them -, the chunk's facts ride as
        ``chunk_*``; ``overlapped``: the program before was still unread;
        ``seq``: the launch's number, which its ``_Flight`` carries to the
        ``engine_wait`` that reads it),
        each live sequence is one token longer, the chunk's bookkeeping is
        done, ``last_step`` counts what the call does. The tokens
        themselves stay on the device until ``_read``."""
        overlapped = int(bool(self._flight))
        seq = self._next_seq()
        n_rows = len(self._slot_tokens)
        chunk_args = {"chunk_tokens": 0}
        if ch is not None:
            n_rows += ch.width
            chunk_args = {"chunk_" + k: v
                          for k, v in self._chunk_args(ch).items()}
            chunk_args.update(self._sparse_args(self._chunk_contexts(ch),
                                                "chunk_"))
            chunk_args.update(self._kv_kind_args(
                [ch.ctx], [len(ch.tokens)], "chunk_"))
            chunk_args.update(self._chunk_tile_args(ch))
            self._ssm_args(1, len(ch.tokens))    # ``last_step``'s count
        with self.tracer.span(
                "decode_step", cat="serving", seq=seq, batch=len(live),
                overlapped=overlapped, **self._moe_args(n_rows),
                **self._row_args(n_rows, len(self._slot_tokens)
                                 + (ch is not None)),
                **self._ssm_args(len(live), len(live)),
                **self._state_args(len(live),
                                   len(ch.tokens) if ch is not None else 0),
                **self._sparse_args([d.seen_tokens + 1 for d in live]),
                **self._kv_kind_args([d.seen_tokens for d in live],
                                     [1] * len(live)),
                **self._past_window_args(live), **chunk_args) as span:
            with self.tracer.span("engine_prep", cat="serving"):
                self._reserve(live, repeat(1))
                extra = self._attn_tile_args(live)
                if ch is not None:
                    table = self._table(ch.desc, len(ch.tokens))
                    fn, pre, post = self._chunk_program(ch, live, table)
                else:
                    # slots hold canonical params: see ``_sampler``
                    rows = any(self._slot_sp[d.slot] != _GREEDY
                               for d in live)
                    fn, pre = self._decode_fn(1, rows), self._slots(live)
                    post = sp_arrays(self._slot_sp) if rows else ()
            t0 = None
            if ch is not None and self._trace_on:
                self._req_compute_begin(ch.uid)  # first chunk ends queue-wait
                t0 = time.monotonic_ns()
            toks, self.cache = self._dispatch(fn, pre, seed, post, prev=True)
            kv = 0
            for d in live:
                d.seen_tokens += 1
                self._slot_lens[d.slot] = d.seen_tokens
                kv += d.seen_tokens
            self.last_step.update(decode_seqs=len(live), kv_tokens=kv,
                                  **extra)
            span.set(kv_tokens=kv, **extra)
            if ch is not None:
                self._chunk_landed(ch, table)
            self._launched(toks, live, ch, t0, seq)
        self.mixed_steps += ch is not None
        self.overlapped_steps += overlapped

    def _launched(self, toks, live, ch: Optional[_Chunk],
                  t0: Optional[int], seq: int) -> None:
        """A decode-shaped program is in flight: its result is what the
        next one resolves its tokens from - the slots of ``live`` their own
        entry, the slot a final chunk seated the last one; every other slot
        the host's copy, which every launch but the newest has filled by
        then (``launch`` leaves at most one program unread)."""
        self._prev = toks
        self._slot_src[:] = _FROM_HOST
        self._slot_src[[d.slot for d in live]] = _FROM_SLOT
        if ch is not None and ch.final:
            self._slot_src[ch.desc.slot] = _FROM_CHUNK
        self._flight.append(_Flight(toks, tuple(live), ch, t0, seq))

    def _read(self, fl: _Flight) -> None:
        """The host's one sync on a launched program: its tokens land on
        their sequences and in ``_out``, in the order the two programs of a
        step had - the chunk's first token, then the decodes'. A sequence
        retired since the launch (a split prefill cancelled by ``finish``
        with a chunk of it in flight) is passed over."""
        with self.tracer.span("engine_wait", cat="serving", seq=fl.seq):
            toks = np.asarray(fl.toks)
        t1 = time.monotonic_ns() if self._trace_on else 0
        seqs = self.state.seqs
        with self.tracer.span("engine_emit", cat="serving"):
            ch = fl.chunk
            if ch is not None:
                final = ch.final and seqs.get(ch.uid) is ch.desc
                if final:
                    self._first_token(ch, int(toks[-1]))
                rec = self._req.get(ch.uid)
                if rec is not None and fl.t0 is not None:
                    # the request's lifecycle keeps its chunk: ring only
                    # (the timeline has the call's one span), no rows on it
                    self.tracer.complete(
                        "prefill_chunk", fl.t0, t1, cat="serving",
                        trace=rec["trace"], parent=rec["span"].span_id,
                        table_blocks=self.state.max_blocks_per_seq,
                        **self._chunk_args(ch), **self._chunk_tile_args(ch))
                if final and self._trace_on:
                    self._req_first_token(ch.uid, t1)
            for d in fl.live:
                if seqs.get(d.uid) is not d:
                    continue
                tok = int(toks[d.slot])
                # the call wrote the KV of the token before (its id is
                # recorded now, so a block it filled is matchable from here
                # on); the new one is pending its write by the next call
                d.tokens.append(d.last_token)
                d.last_token = tok
                d.generated.append(tok)
                self._slot_tokens[d.slot] = tok
                self.state.mark_filled(d)
                self._out.setdefault(d.uid, []).append(tok)
                if self._trace_on:
                    self._req_tokens(d.uid, 1, t1)

    def _next_seq(self) -> int:
        """The number of the program about to be launched."""
        self._seq += 1
        return self._seq

    def drain(self, cause: str) -> None:
        """Read every program in flight: what needs a token's VALUE or
        moves a sequence calls this first, and says why (``cause``, one of
        ``telemetry.schema.DRAIN_CAUSES``: ``put``, ``park``, ``fork``, a
        speculative step, ...). The tokens wait in ``_out`` for the next
        ``collect``. With nothing in flight it is nothing: no span, no
        count - the synchronous paths stay event-free."""
        if not self._flight:
            return
        self.drains[cause] += 1
        with self.tracer.span("engine_drain", cat="serving", cause=cause):
            while self._flight:
                self._read(self._flight.popleft())

    @property
    def in_flight(self) -> int:
        """Launched programs the host has not read."""
        return len(self._flight)

    def _flying(self) -> Iterator[int]:
        """The uids the launched, unread programs hold a token for, one
        entry a token."""
        for fl in self._flight:
            yield from (d.uid for d in fl.live)
            if fl.chunk is not None and fl.chunk.final:
                yield fl.chunk.uid

    def tokens_uncollected(self) -> Dict[int, int]:
        """{uid: tokens that launched programs hold for it, or that a drain
        has read, and no ``collect`` has handed out}: a scheduler adds them
        to a stream's length to know, at launch, which streams are at their
        end."""
        n = Counter({u: len(toks) for u, toks in self._out.items()})
        n.update(self._flying())
        return n

    def forget_flight(self) -> None:
        """Drop what was launched and not read, UNREAD: an abandoned
        replica's device is asked for nothing (``ServingScheduler.
        abandon_all``, which retires every sequence next). The tokens were
        never handed out; whoever continues the streams samples them
        again."""
        self._flight.clear()
        self._out.clear()
        self._slot_src[:] = _FROM_HOST

    def launch(self, seed: int = 0, hold=()) -> int:
        """The first half of ``step()``: advance the oldest split prefill
        by one chunk and dispatch one decode step over every live sequence
        but those of ``hold`` (uids the caller knows to be at their end: a
        count, not a value), WITHOUT reading a token - every live sequence
        advances by one, so the next launch needs nothing of this one's
        result but the tokens, and those it takes on the device. Returns
        the number of programs it left for ``collect`` (0 or 1; a prompt's
        final chunk is one more under speculation, which runs it apart).
        At most one program stays unread across a launch; a speculative
        step reads its sequences' history and so runs whole here (its
        tokens wait for ``collect``)."""
        self.steps += 1
        if self._spec_on:
            self.drain("spec")
        while len(self._flight) > 1:
            self._read(self._flight.popleft())
        before = len(self._flight)
        live, chunk = self._prefill_then_live(seed, not self._spec_on, hold)
        if chunk is not None:
            self._launch_decode(live, seed, chunk)
        elif live and not (self._spec_on and self._spec_step(live, seed)):
            if self._spec_on:
                # no sequence drafted this step: run the plain decode
                # program — bit-identical to a non-spec step, and cheaper
                # than a k+1-wide verify batch with one valid column
                self.spec_stats["decode_steps"] += 1
                self.spec_stats["step_seqs"] += len(live)
                self.spec_stats["emitted_tokens"] += len(live)
            self._launch_decode(live, seed)
        return len(self._flight) - before

    def collect(self, ahead: int = 0) -> Dict[int, List[int]]:
        """The second half of ``step()``: read the programs in flight, all
        but the newest ``ahead``, and hand out {uid: [tokens]} - theirs and
        whatever a drain read since the last call, each sequence's in
        order."""
        while len(self._flight) > ahead:
            self._read(self._flight.popleft())
        out, self._out = self._out, {}
        return out

    def step(self, sp: SamplingParams = SamplingParams(greedy=True),
             seed: int = 0) -> Dict[int, int]:
        """One decode step over every live sequence → {uid: next_token}:
        ``launch`` followed at once by ``collect``. Split-admitted sequences
        advance one prefill chunk first; a sequence whose prompt completes
        this step contributes its first token.

        Sampling uses each sequence's ADMISSION-time params (per-request
        sampling, like the reference v2 engine); the ``sp`` argument is
        accepted for backward compatibility and ignored.

        With ``inference.speculative.enabled`` the step drafts + verifies
        instead (``_spec_step``) and may emit SEVERAL tokens per sequence, so
        the return type widens to {uid: [tokens]} — every value is a list,
        including prefill first-tokens and draft-less fallback steps."""
        self._warn_ignored_sp(sp)
        if not self._spec_on and (self._flight or self._out):
            raise RuntimeError(
                "step() returns one token a sequence: collect() what "
                "launch() left in flight first")
        self.launch(seed)
        out = self.collect()
        return out if self._spec_on else {u: s[0] for u, s in out.items()}

    def step_many(self, k: int, sp: SamplingParams = SamplingParams(greedy=True),
                  seed: int = 0) -> Dict[int, List[int]]:
        """k decode steps over every live sequence with ONE host sync →
        {uid: [k next tokens]}. Tokens sampled after a sequence's EOS are
        still produced (the caller trims) — the standard multi-step decode
        trade. k is clamped so no live sequence can run past max_seq_len.
        Split-admitted sequences advance one prefill chunk per quantum; a
        prompt completing here contributes its first token as a 1-list.

        Speculative decoding does NOT apply here: the fused k-step scan is
        the alternative host-sync amortization (fixed k tokens per sync);
        drafting+verification lives in ``step()``, which emits a variable
        number of tokens per call. ``generate`` picks ``step()`` when
        ``inference.speculative.enabled`` is set."""
        self._warn_ignored_sp(sp)
        self.drain("quantum")
        live, _ = self._prefill_then_live(seed)
        if live:
            # a tick at seen writes KV position seen, so seen may reach
            # exactly max_seq_len after the last tick — same boundary as the
            # per-step path (which decodes while seen == max_seq_len - 1)
            k = min(k, self.family.cfg.max_seq_len
                    - max(d.seen_tokens for d in live))
        if live and k > 0:
            seq = self._next_seq()
            with self.tracer.span("decode_quantum", cat="serving", seq=seq,
                                  k=k, batch=len(live)) as span:
                self._decode_quantum(k, live, seed, span, seq)
        return self.collect()

    def finish(self, uid: int) -> List[int]:
        """Retire a sequence, free its blocks, return generated tokens.
        An unknown or already-finished uid raises
        :class:`~deepspeed_tpu.inference.ragged.UnknownSequenceError` with
        the uid in the message (one consistent error, whichever internal
        structure would have missed first)."""
        desc = self.state.lookup(uid)
        if uid in self._flying():
            # its stream is whole before it is handed back
            self.drain("finish")
        self._req_finish(uid, generated=len(desc.generated))
        self._pending_prefill.pop(uid, None)  # cancel an in-flight split
        self._clear_slot(desc.slot)
        self.state.retire(uid)
        return desc.generated

    def _clear_slot(self, s: int) -> None:
        self._slot_lens[s] = 0
        self._slot_tables[s] = 0
        self._slot_sp[s] = _GREEDY

    # ------------------------------------------------------------------ #
    # scheduler seams: KV headroom + decode preemption (park/resume) —
    # docs/serving.md "Scheduler & router"
    # ------------------------------------------------------------------ #
    def kv_headroom(self) -> Dict[str, int]:
        """Admission-control snapshot for a scheduler: free/retained/total
        KV blocks and free sequence slots. ``headroom_blocks`` is the number
        an admission could actually obtain (retained prefix blocks are
        evicted on demand)."""
        st = self.state
        out = {"free_blocks": st.allocator.free_blocks,
               "retained_blocks": st.retained_blocks,
               "headroom_blocks": st.headroom_blocks,
               "free_slots": st.free_slots,
               "total_blocks": st.allocator.num_blocks - 1}
        # the bytes of every leaf with a block axis: 0 where the family's
        # state is all it keeps, and then no block is there to count
        out["kv_bytes"] = self._kv_bytes
        if self._blockless:
            out.update(free_blocks=0, headroom_blocks=0, total_blocks=0)
        if self._indexed:
            # a block is a page of every pool, the index keys' among them
            out["block_bytes"] = sum(
                leaf.nbytes // leaf.shape[1]
                for leaf in jax.tree.leaves(self.cache))
        if self._recurrent:
            # a slot is its state row: what a free slot stands for, in bytes
            out.update(state_bytes_per_slot=st.state_slot_bytes,
                       state_bytes_free=st.state_bytes_free,
                       state_bytes_total=st.max_sequences
                       * st.state_slot_bytes)
        return out

    def set_speculative(self, enabled: bool) -> bool:
        """Runtime toggle for speculative decoding — the overload
        degradation ladder's level-2 action (docs/serving.md "Fleet fault
        tolerance"): under KV pressure the verify window's extra positions
        stop competing for blocks. Safe between steps (speculation never
        spans a step); turning it off routes ``step()`` through the exact
        plain decode programs. Cannot enable what the config never
        configured. Returns the previous setting so the caller can restore
        it exactly."""
        prev = self._spec_on
        self._spec_on = bool(enabled) and bool(self.config.speculative.enabled)
        return prev

    def park(self, uid: int) -> Dict[str, Any]:
        """Preempt a sequence: capture everything needed to continue it
        later, then release its slot and KV blocks. With the prefix cache
        enabled the victim's full blocks park in the retained LRU pool, so
        :meth:`resume` re-prefills only what eviction reclaimed in between;
        with the cache off, resume re-prefills the whole history. The
        request's trace record stays open (park/resume is invisible to the
        client except as latency), and an instant marks the gap."""
        desc = self.state.lookup(uid)
        self.drain("park")  # the history is every token, those in flight too
        self._pending_prefill.pop(uid, None)   # mid-split park: chunks stop
        history = list(desc.tokens) if desc.prefilling \
            else list(desc.tokens) + [desc.last_token]
        parked = {"uid": uid, "history": history,
                  "generated": list(desc.generated),
                  "prompt_len": len(history) - len(desc.generated),
                  "sp": self._slot_sp[desc.slot]}
        self._clear_slot(desc.slot)
        self.state.retire(uid)
        if self._trace_on:
            rec = self._req.get(uid)
            self.tracer.instant(
                "parked", cat="serving",
                trace=rec["trace"] if rec else None,
                parent=rec["span"].span_id if rec else None,
                uid=uid, kv_tokens=len(history))
        return parked

    def resume(self, parked: Dict[str, Any], seed: int = 0,
               split: bool = False) -> List[int]:
        """Re-admit a :meth:`park`-ed sequence and continue its stream:
        the full history (prompt + every generated token) is re-prefilled —
        resolving retained blocks through the prefix cache when enabled —
        and the first token sampled afterwards is exactly the next stream
        token, so a greedy park/resume cycle is token-identical to an
        uninterrupted run (pinned by tests). Returns the newly emitted
        tokens: one for a one-shot resume, ``[]`` when ``split=True``
        defers the prompt to chunked prefill (the token then arrives from
        a later ``step()``). ``generated`` continuity is restored, so
        ``finish()`` returns the complete stream."""
        uid, sp = parked["uid"], parked["sp"]
        history = parked["history"]
        if split:
            self.put_split(uid, history, sp)
            self.state.seqs[uid].generated = list(parked["generated"])
            if self._trace_on:
                self._resume_instant(uid, split=True)
            return []
        tok = self.put(uid, history, sp, seed=seed)
        self.state.seqs[uid].generated = list(parked["generated"]) + [tok]
        if self._trace_on:
            self._resume_instant(uid, split=False)
        return [tok]

    def _resume_instant(self, uid: int, split: bool) -> None:
        rec = self._req.get(uid)
        self.tracer.instant("resumed", cat="serving",
                            trace=rec["trace"] if rec else None,
                            parent=rec["span"].span_id if rec else None,
                            uid=uid, split=split)

    def fork(self, uid: int, new_uid: int,
             sp: Optional[SamplingParams] = None):
        """Fork a live sequence: ``new_uid`` decodes from the SAME context
        without copying a single KV byte (parallel sampling / best-of-n).
        Both sequences share every block including the partial tail —
        whichever appends first gets a private copy via copy-on-write. The
        child starts with an empty ``generated`` list and, unless ``sp`` is
        given, the parent's sampling params."""
        self._refuse_call("fork", "fork")
        self.drain("fork")  # the child starts from the parent's last token
        desc = self.state.fork(uid, new_uid)
        self._req_admit(new_uid, desc.seen_tokens)
        self._seat(desc, self.state.block_table(desc),
                   self._canon_sp(sp) if sp is not None
                   else self._slot_sp[self.state.seqs[uid].slot])
        return desc

    # ------------------------------------------------------------------ #
    # Disaggregated prefill → decode handoff (docs/serving.md
    # "Disaggregated prefill/decode"). A prefill-tier replica finishes a
    # prompt, then its router ships the sequence's FULL chain-hashed KV
    # blocks to a decode-tier replica: export reads block slices off the
    # paged pool (optionally re-coding them to the int8+scales wire
    # format), import lands them in the destination's retained prefix
    # pool keyed by the same chain hashes, and the parked request resumes
    # there — ``admit_prompt`` resolves the imported blocks as an
    # admit-time hit, so only the partial tail block is re-prefilled.

    def kv_chain_hashes(self, uid: int) -> List[bytes]:
        """Chain hashes of ``uid``'s full KV blocks, indexing any newly
        full blocks first — the handoff planner keys the wire transfer
        (and the destination's dedup probe) on these."""
        desc = self.state.lookup(uid)
        # a block a decode filled is hashed from its ids
        self.drain("prefix_hash")
        self.state.mark_filled(desc)
        return list(desc.block_hashes)

    def resident_prefix(self, chain_hashes: List[bytes]) -> int:
        """How many LEADING entries of ``chain_hashes`` are already
        canonical in this engine's prefix index. The handoff planner skips
        shipping those blocks: a destination-resident shared prefix never
        crosses the wire (the probe is advisory — eviction between probe
        and resume only costs re-prefill, never correctness)."""
        if not self.state.prefix_cache:
            return 0
        return len(self.state.index.match(list(chain_hashes)))

    def export_kv_blocks(self, uid: int, skip: int = 0,
                         wire: str = "native",
                         wire_group: int = 64) -> Dict[str, Any]:
        """Read ``uid``'s full KV blocks after ``skip`` off the paged pool
        as host arrays for a prefill→decode handoff. Must be called while
        the sequence is still tracked (i.e. BEFORE ``park``).

        Wire formats (docs/serving.md):

        - ``"native"`` — cache leaves verbatim (bitwise). On a quantized-KV
          engine this already IS int8 codes + fp32 group scales, i.e. the
          half-width wire format for free;
        - ``"int8"`` — a bf16/fp32 engine re-codes k/v to int8 codes +
          fp32 per-``wire_group`` scales at the seam, halving wire bytes
          (lossy at the handoff boundary — greedy token-identity pins use
          bitwise configurations). On a quantized engine this is a no-op
          alias for ``"native"``.

        Returns ``{"uid", "hashes", "skip", "blocks", "wire_bytes",
        "bf16_equiv_bytes", "block_wire_bytes"}`` where
        ``bf16_equiv_bytes`` is what the same blocks would cost as 2-byte
        k/v (the wire-ratio denominator) and ``block_wire_bytes`` is one
        block's wire footprint — what each ``skip``-ped (dedup'd) block
        did NOT cost."""
        if wire not in ("native", "int8"):
            raise ValueError(f"unknown KV wire format {wire!r}")
        self._refuse_call("export_kv_blocks", "handoff")
        desc = self.state.lookup(uid)
        self.drain("export")   # the blocks hold every token's KV first
        self.state.mark_filled(desc)
        hashes = list(desc.block_hashes)
        skip = max(0, min(int(skip), len(hashes)))
        quantize = wire == "int8" and not self._kvq_on
        if quantize:
            hd = self.family.cfg.head_size
            wire_group = min(int(wire_group), hd)
            if wire_group < 1 or hd % wire_group:
                raise ValueError(
                    f"wire_group {wire_group} does not divide "
                    f"head_size {hd}")
        per_block = 0
        for n in sorted(self.cache):
            leaf = self.cache[n]
            elems = int(np.prod(leaf.shape)) // int(leaf.shape[1])
            if quantize and n in ("k", "v"):
                per_block += elems + (elems // wire_group) * 4
            else:
                per_block += elems * leaf.dtype.itemsize
        blocks: List[Dict[str, np.ndarray]] = []
        wire_bytes = 0
        bf16_equiv = 0
        for h, b in zip(hashes[skip:], desc.blocks[skip:len(hashes)]):
            payload = {n: np.asarray(self.cache[n][:, b])
                       for n in sorted(self.cache)}
            # int8 codes mirror the bf16 element count, so k/v sizes give
            # the bf16-equivalent bytes in every wire mode
            bf16_equiv += 2 * (payload["k"].size + payload["v"].size)
            if quantize:
                for n in ("k", "v"):
                    codes, scales = kv_quantize_int8(
                        jnp.asarray(payload[n]), wire_group)
                    payload[n] = np.asarray(codes)
                    payload[n + "_scale"] = np.asarray(scales)
            wire_bytes += sum(a.nbytes for a in payload.values())
            blocks.append(payload)
        return {"uid": uid, "hashes": hashes[skip:], "skip": skip,
                "blocks": blocks, "wire_bytes": wire_bytes,
                "bf16_equiv_bytes": bf16_equiv,
                "block_wire_bytes": per_block}

    def import_kv_blocks(self, chain_hashes: List[bytes],
                         blocks: List[Dict[str, np.ndarray]]) -> Dict[str, int]:
        """Land exported KV blocks in THIS engine's retained prefix pool,
        keyed by their chain hashes. Per block: already-canonical hashes
        are deduplicated (the probe raced a concurrent admission), the
        rest adopt a retained block via ``StateManager.adopt_block`` and
        stamp the converted payload into the device pool. A dropped block
        (pool exhausted / retention off) is harmless — resume re-prefills
        that suffix. Returns ``{"imported", "dedup", "dropped"}``."""
        self._refuse_call("import_kv_blocks", "handoff")
        res = {"imported": 0, "dedup": 0, "dropped": 0}
        for h, payload in zip(chain_hashes, blocks):
            if self.state.prefix_cache and h in self.state.index._by_hash:
                res["dedup"] += 1
                continue
            blk = self.state.adopt_block(h)
            if blk is None:
                res["dropped"] += 1
                continue
            self._spill_write_block(blk, self._wire_to_cache(payload))
            res["imported"] += 1
        return res

    def _wire_to_cache(self, payload: Dict[str, np.ndarray]) -> List[Any]:
        """Convert one wire-format block payload to this engine's cache
        leaf order (``jax.tree.leaves`` = sorted keys). Matching formats
        pass through bitwise; int8 wire dequantizes into a float pool;
        float wire (or a mismatched scale grouping) re-quantizes into a
        quantized pool at the local group size."""
        keys = sorted(self.cache.keys())
        wired_int8 = "k_scale" in payload
        if self._kvq_on:
            ng = self.family.cfg.head_size // self._kvq_group
            if wired_int8 and payload["k_scale"].shape[-1] == ng:
                return [payload[k] for k in keys]           # bitwise
            conv: Dict[str, Any] = {}
            for n in ("k", "v"):
                x = (kv_dequantize_int8(jnp.asarray(payload[n]),
                                        jnp.asarray(payload[n + "_scale"]))
                     if wired_int8 else jnp.asarray(payload[n]))
                conv[n], conv[n + "_scale"] = kv_quantize_int8(
                    x, self._kvq_group)
            return [conv[k] for k in keys]
        if wired_int8:
            dt = self.cache["k"].dtype
            return [kv_dequantize_int8(jnp.asarray(payload[n]),
                                       jnp.asarray(payload[n + "_scale"]),
                                       dtype=dt) for n in keys]
        return [payload[k] for k in keys]                   # bitwise

    # ------------------------------------------------------------------ #
    def prefix_cache_events(self, step: int = 0):
        """``Serving/prefix_cache/*`` telemetry events (cumulative counters
        plus the retained-pool occupancy gauge) — written through an attached
        TelemetryHub by :meth:`publish_prefix_telemetry`, or directly by the
        serving bench's JSONL sink for ``telemetry_report.py --serving``."""
        stats = dict(self.state.prefix_stats)
        stats["retained_blocks"] = self.state.retained_blocks
        if self._kv_spill is not None:
            stats["spilled_blocks"] = self._kv_spill.spilled_blocks
        return [(f"Serving/prefix_cache/{k}", float(v), step)
                for k, v in sorted(stats.items())]

    def _publish(self, events, sink: str = "serving_event"):
        """Hand ``events`` to the attached hub's ``sink``, if there is one."""
        if self._hub is not None:
            send = getattr(self._hub, sink)
            for name, value, s in events:
                send(name, value, s)
        return events

    def publish_prefix_telemetry(self, step: int = 0):
        events = self._publish(self.prefix_cache_events(step))
        if self._hub is not None and self._kv_spill is not None:
            # the host pool is a memory TIER — its occupancy also lands in
            # the closed Memory/tier/* family beside the training store's
            # gauges (telemetry_report.py --memory)
            pool = self._kv_spill
            for k, v in (("kv_spilled_blocks", pool.spilled_blocks),
                         ("kv_spilled_bytes", pool.spilled_bytes),
                         ("kv_spills", pool.stats["spills"]),
                         ("kv_restores", pool.stats["restores"])):
                self._hub.memory_tier_event(k, float(v), step)
        return events

    # ------------------------------------------------------------------ #
    def kv_quant_events(self, step: int = 0):
        """``Serving/kv_quant/*`` telemetry events (quantized-KV mode only;
        docs/serving.md "Quantized KV cache"):

        - ``blocks_quantized``: blocks currently resident holding int8 KV
          (live + retained — everything off the free list);
        - ``bytes_saved``: device bytes those blocks DON'T occupy vs a bf16
          pool of the same block count (int8 codes + fp32 scales vs 2-byte
          codes);
        - ``max_abs_err``: upper bound on the per-element dequantization
          error over the whole pool — symmetric rounding errs by at most
          half a quantization step, so ``max(scale) / 2`` (unwritten
          positions hold zero scales and cannot inflate it);
        - ``dequant_fused``: 1.0 — asserts the serving programs dequantize
          inside the attention kernels, never as a standalone convert pass
          (the QUANT_TPU_LIVE-losing path)."""
        if not self._kvq_on:
            return []
        resident = (self.state.allocator.num_blocks - 1
                    - self.state.allocator.free_blocks)
        code_elems = scale_elems = 0
        max_scale = 0.0
        for name in ("k", "v"):
            c = self.cache[name]
            code_elems += c.size // c.shape[1]          # per-block elements
            s = self.cache[name + "_scale"]
            scale_elems += s.size // s.shape[1]
            max_scale = max(max_scale, float(jnp.max(s)))
        saved_per_block = 2 * code_elems - (code_elems + 4 * scale_elems)
        vals = {"blocks_quantized": float(resident),
                "bytes_saved": float(saved_per_block * resident),
                "max_abs_err": 0.5 * max_scale,
                "dequant_fused": 1.0}
        return [(f"Serving/kv_quant/{k}", float(v), step)
                for k, v in sorted(vals.items())]

    def publish_kv_quant_telemetry(self, step: int = 0):
        return self._publish(self.kv_quant_events(step))

    def state_events(self, step: int = 0):
        """``Serving/state/*`` telemetry events of a family with recurrent
        state (none for any other): ``bytes``, the size of the per-slot state
        pools as they lie on the device (every slot's row and the trash row,
        every state-space layer); ``bytes_per_slot``, what one sequence
        holds; ``slots_held``, the slots sequences hold now."""
        if not self._recurrent:
            return []
        st = self.state
        vals = {"bytes": sum(self.cache[n].nbytes
                             for n in self.family.state_leaves),
                "bytes_per_slot": st.state_slot_bytes,
                "slots_held": st.max_sequences - st.free_slots}
        return [(f"Serving/state/{k}", float(v), step)
                for k, v in sorted(vals.items())]

    def publish_state_telemetry(self, step: int = 0):
        return self._publish(self.state_events(step))

    def kv_kind_events(self, step: int = 0):
        """``Serving/kv/*`` telemetry events. Of a family with a latent
        cache: ``latent_blocks_live``, the blocks sequences hold of its one
        pool now. Of a family with window layers'
        KV state (none for any other): ``full_blocks_live`` and
        ``window_blocks_live``, the blocks sequences hold of each kind now,
        and ``window_blocks_released``, the window kinds' blocks given back
        behind the window, cumulative."""
        st = self.state
        if self._latent:
            return [("Serving/kv/latent_blocks_live",
                     float(self.blocks_live()), step)]
        if not self._window:
            return []
        vals = {"full_blocks_live": self.blocks_live(),
                "window_blocks_live": sum(st.window_blocks_live(k.name)
                                          for k in st.window_kinds),
                "window_blocks_released": st.window_blocks_released}
        return [(f"Serving/kv/{k}", float(v), step)
                for k, v in sorted(vals.items())]

    def blocks_live(self) -> int:
        """Blocks of the full kind's pool (a latent cache's one pool) that
        sequences hold now."""
        alloc = self.state.allocator
        return alloc.num_blocks - 1 - alloc.free_blocks \
            - self.state.retained_blocks

    def publish_kv_kind_telemetry(self, step: int = 0):
        return self._publish(self.kv_kind_events(step))

    def sparse_events(self, step: int = 0):
        """``Serving/sparse/*`` telemetry events of a family with a learned
        token selection (none for any other), cumulative and ONE layer's
        count: ``rows`` that selected, ``ctx_scored``, the cached tokens
        their indexers scored, ``kv_selected``, the tokens attention read."""
        if not self._indexed:
            return []
        return [(f"Serving/sparse/{k}", float(v), step)
                for k, v in sorted(self.sparse_stats.items())]

    def publish_sparse_telemetry(self, step: int = 0):
        return self._publish(self.sparse_events(step))

    def engine_events(self, step: int = 0):
        """``Serving/engine/*`` telemetry events (cumulative): ``steps``,
        the steps launched; ``mixed_steps``, those that ran their prefill
        chunk and their decodes as one program (``decode_chunk``: a pending
        chunk met live decodes in a family that takes a mixed call); and
        ``overlapped_steps``, those whose decode program was launched while
        the one before was still unread (a scheduler's ticks; 0 through
        ``step()`` alone); ``rows``, the token rows the launched prefill
        and decode programs ran (``prefill_batch``, ``prefill_chunk``,
        ``decode_step``; padding included), and ``head_rows``, the rows
        their heads scored - what each program reads, where the family's
        ``apply_paged`` takes ``rows``."""
        return [("Serving/engine/" + name, float(value), step)
                for name, value in (
                    ("steps", self.steps),
                    ("mixed_steps", self.mixed_steps),
                    ("overlapped_steps", self.overlapped_steps),
                    ("rows", self.rows),
                    ("head_rows", self.head_rows))]

    def publish_engine_telemetry(self, step: int = 0):
        return self._publish(self.engine_events(step))

    def debug_check_cache(self) -> None:
        """Cache-pytree invariants beside ``StateManager.debug_check`` —
        in quantized-KV mode the scale tables must stay consistent with the
        code pools through every block-lifecycle op (COW, fork, truncate,
        spill/restore): int8 codes, fp32 scales, one scale vector per
        (block, head, token) with ``head_size // group_size`` groups, all
        finite and non-negative. Raises AssertionError on violation."""
        keys = set(self.cache.keys()) - set(self.family.state_leaves) \
            - {f"{kv}_{kind}" for kind in self._window for kv in "kv"}
        if self._blockless:
            assert not keys, f"a cache with no block pool has leaves {keys}"
            return
        if not self._kvq_on:
            assert keys == {"k", "v"}, \
                f"unquantized cache has unexpected leaves {keys}"
            return
        assert keys == {"k", "v", "k_scale", "v_scale"}, \
            f"quantized cache has unexpected leaves {keys}"
        hd = self.family.cfg.head_size
        ng = hd // self._kvq_group
        for name in ("k", "v"):
            c, s = self.cache[name], self.cache[name + "_scale"]
            assert c.dtype == jnp.int8, f"{name} codes are {c.dtype}"
            assert s.dtype == jnp.float32, f"{name} scales are {s.dtype}"
            assert s.shape == c.shape[:-1] + (ng,), \
                f"{name}_scale shape {s.shape} inconsistent with codes " \
                f"{c.shape} at group_size {self._kvq_group}"
            smin, smax = float(jnp.min(s)), float(jnp.max(s))
            assert np.isfinite(smax) and smin >= 0.0, \
                f"{name}_scale range [{smin}, {smax}] invalid"

    # ------------------------------------------------------------------ #
    def spec_events(self, step: int = 0):
        """``Serving/spec/*`` telemetry events: the cumulative counters plus
        the derived efficiency gauges — ``accept_rate`` (accepted / drafted),
        ``mean_accepted_len`` (accepted per verify step), ``tokens_per_step``
        (emitted tokens per live sequence per model forward pass — the
        headline: > 1 means decode is beating one-token-per-pass; the
        per-sequence normalization keeps batch size out of the number), and
        ``verify_batch_occupancy`` (valid verify positions / batch
        capacity). All names are registered in ``telemetry/schema.py``."""
        s = self.spec_stats
        vals: Dict[str, float] = {k: float(v) for k, v in s.items()}
        vals["accept_rate"] = (s["accepted_tokens"] / s["drafted_tokens"]
                               if s["drafted_tokens"] else 0.0)
        vals["mean_accepted_len"] = (s["accepted_tokens"] / s["verify_steps"]
                                     if s["verify_steps"] else 0.0)
        vals["tokens_per_step"] = (s["emitted_tokens"] / s["step_seqs"]
                                   if s["step_seqs"] else 0.0)
        vals["verify_batch_occupancy"] = (
            s["verify_positions"] / s["verify_capacity"]
            if s["verify_capacity"] else 0.0)
        return [(f"Serving/spec/{k}", float(v), step)
                for k, v in sorted(vals.items())]

    def publish_spec_telemetry(self, step: int = 0):
        return self._publish(self.spec_events(step))

    # ------------------------------------------------------------------ #
    # latency SLOs: TTFT / inter-token latency / queue time / e2e, with
    # p50/p90/p99 (docs/serving.md). Samples accumulate while tracing is on.
    # ------------------------------------------------------------------ #
    def latency_summary(self) -> Dict[str, Dict[str, float]]:
        """{metric: {"p50", "p90", "p99", "mean", "count"}} in ms."""
        out: Dict[str, Dict[str, float]] = {}
        for metric, vals in self._lat.items():
            stats = percentiles(vals, (50, 90, 99))
            stats["count"] = float(len(vals))
            stats["mean"] = (sum(vals) / len(vals)) if vals else 0.0
            out[metric] = stats
        return out

    def latency_events(self, step: int = 0):
        """``Serving/latency/*`` telemetry events (gauges: last sample wins,
        like the prefix-cache counters)."""
        events = []
        for metric, stats in sorted(self.latency_summary().items()):
            for key in ("p50", "p90", "p99", "count"):
                events.append((f"Serving/latency/{metric}_{key}",
                               float(stats[key]), step))
        return events

    def publish_latency_telemetry(self, step: int = 0):
        return self._publish(self.latency_events(step))

    def compile_events(self, step: int = 0):
        """Drain the compile monitor: cumulative ``Compile/*`` counters per
        paged program (prefill/decode/verify families: compiles, cache
        hits, RECOMPILES, lower/compile wall time, cost-model flops) plus
        ``Serving/mfu/<program>`` attribution gauges over the wall window
        since this caller's previous drain. The drain is scoped to the
        ``Serving`` group so a hub-shared monitor keeps its training-side
        counters and step-time windows intact (and vice versa). Names are
        registered in ``telemetry/schema.py``."""
        return self.compile_monitor.events(step, group="Serving")

    def publish_compile_telemetry(self, step: int = 0):
        return self._publish(self.compile_events(step), "compile_event")

    def export_trace(self, path: str):
        """Dump the flight recorder as Chrome-trace/Perfetto JSON."""
        return self.tracer.export(path)

    # ------------------------------------------------------------------ #
    def generate(self, prompts, max_new_tokens: int = 64,
                 eos_token_id: Optional[int] = None, seed: int = 0,
                 temperature: float = 0.0, top_k: int = 0, top_p: float = 1.0,
                 prompt_lengths=None, steps_per_sync: int = 1,
                 sampling_params=None) -> List[List[int]]:
        """Continuous-batching driver: admit prompts as capacity allows,
        decode all live sequences each step. Returns generated ids per prompt.

        ``steps_per_sync > 1`` runs that many decode ticks per compiled call
        (one host round-trip per quantum instead of per token — the serving
        fast path); admission and EOS retirement happen at quantum
        boundaries, and completions are trimmed to the first EOS exactly as
        in the per-step path.

        ``sampling_params``: optional list of per-PROMPT SamplingParams
        (overrides the scalar temperature/top_k/top_p args) — each request
        decodes under its own config in the shared batch."""
        sp = SamplingParams(temperature=temperature, top_k=top_k, top_p=top_p,
                            greedy=temperature == 0.0)
        if sampling_params is not None:
            if len(sampling_params) != len(prompts):
                raise ValueError(
                    f"{len(sampling_params)} sampling_params for "
                    f"{len(prompts)} prompts")
            sp_for = list(sampling_params)
        else:
            sp_for = [sp] * len(prompts)
        prompts = [np.asarray(p, np.int32) for p in prompts]
        if prompt_lengths is not None:
            prompts = [p[:n] for p, n in zip(prompts, prompt_lengths)]
        pending = list(enumerate(prompts))
        results: Dict[int, List[int]] = {}
        # reject prompts that can NEVER be admitted (need more blocks than the
        # pool holds even when empty) instead of spinning forever
        capacity = self.state.allocator.num_blocks - 1
        for _, p in pending:
            need = self.state.blocks_needed(len(p))
            if need > capacity:
                raise MemoryError(
                    f"prompt of {len(p)} tokens needs {need} KV blocks but the "
                    f"pool only holds {capacity}; raise ragged.memory_config_blocks")
        step_i = 0
        while pending or self.state.seqs:
            batch_adm = []
            batch_cached = []
            split = self.config.split_prefill_chunk
            # the offline burst: nothing is in flight here and no live
            # stream waits on a tick, so a prompt that fits one EFFECTIVE
            # chunk stays in the batched one-shot prefill (many prompts, one
            # program). A serving tick decides otherwise where a program is
            # in flight: ``ServingScheduler._takes_chunk_lane``
            eff_chunk = (_round_up(split, self.config.prefill_bucket)
                         if split > 0 else 0)
            while pending and self.state.can_admit(len(pending[0][1])):
                uid, prompt = pending.pop(0)
                if split > 0 and len(prompt) > eff_chunk:
                    # SplitFuse path: the prompt enters chunk-by-chunk inside
                    # the step calls below, never stalling live decodes
                    self.put_split(uid, prompt, sp_for[uid])
                    continue
                # admit eagerly so can_admit sees each admission's capacity
                desc, hit = self.state.admit_prompt(uid, prompt)
                batch_adm.append((uid, prompt, desc))
                batch_cached.append(hit)
            if batch_adm:  # one compiled prefill for the whole burst
                self._prefill_admitted(
                    batch_adm, [sp_for[uid] for uid, _, _ in batch_adm],
                    seed=seed, cached=batch_cached)
            if steps_per_sync > 1 and not self._spec_on:
                k = max(1, min(steps_per_sync, max_new_tokens))
                self.step_many(k, seed=seed + step_i)
                step_i += k
            else:
                # spec mode always steps here: a verify step already emits
                # multiple tokens per host sync, subsuming steps_per_sync
                self.step(seed=seed + step_i)
                step_i += 1
            for uid in list(self.state.seqs):
                d = self.state.seqs[uid]
                if d.prefilling:
                    continue  # no tokens yet — nothing to retire on
                if eos_token_id is not None and eos_token_id in d.generated:
                    # trim overshoot past the first EOS (multi-step quantum)
                    d.generated = d.generated[:d.generated.index(eos_token_id) + 1]
                    d.last_token = d.generated[-1]
                hit_eos = eos_token_id is not None and d.last_token == eos_token_id
                # retire at seen == max_seq_len: KV positions 0..max-1 are
                # then all used (a decode at lens == max-1 writes the LAST
                # slot — the old `seen+1 >= max` check wasted it, and made
                # the per-step and fused-quantum paths disagree by a token)
                if len(d.generated) >= max_new_tokens or hit_eos or \
                        d.seen_tokens >= self.family.cfg.max_seq_len:
                    d.generated = d.generated[:max_new_tokens]
                    results[uid] = self.finish(uid)
        if self._trace_on:
            # a hub-attached run lands its SLO percentiles in the monitor
            # stream for telemetry_report.py --latency; trace off → no events
            self.publish_latency_telemetry(step_i)
        if self._spec_on and self._hub is not None:
            self.publish_spec_telemetry(step_i)
        if self._kvq_on and self._hub is not None:
            self.publish_kv_quant_telemetry(step_i)
        if self._recurrent and self._hub is not None:
            self.publish_state_telemetry(step_i)
        if self._indexed and self._hub is not None:
            self.publish_sparse_telemetry(step_i)
        if self._hub is not None:
            self.publish_engine_telemetry(step_i)
        if self.compile_monitor.enabled and self._hub is not None:
            self.publish_compile_telemetry(step_i)
        return [results[i] for i in range(len(prompts))]


def build_engine_v2(model, model_cfg, params, config=None,
                    telemetry_hub=None, **kwargs) -> InferenceEngineV2:
    """Counterpart of ``build_hf_engine`` (``inference/v2/engine_factory.py:70``)."""
    if isinstance(config, dict) or config is None:
        config = InferenceConfig.from_dict({**(config or {}), **kwargs})
    family = ModelFamily.from_module(model, model_cfg)
    return InferenceEngineV2(
        family, params, config,
        init_paged_cache=getattr(model, "init_paged_cache", None),
        apply_paged=getattr(model, "apply_paged", None),
        telemetry_hub=telemetry_hub)


def build_hf_engine(checkpoint: str, config=None,
                    **kwargs) -> InferenceEngineV2:
    """One call from a local HF checkpoint directory to a continuous-batching
    engine (the reference's ``engine_factory.build_hf_engine`` entry:
    resolve family → import weights → construct the v2 engine)."""
    from ..models.hf_import import load_checkpoint_dir_module

    fam, model, model_cfg, params = load_checkpoint_dir_module(checkpoint)
    if not hasattr(model, "apply_paged"):
        # the engine runs the paged block-table path — gating on the weaker
        # apply_cached would fall through to llama's kernels on a foreign
        # config/param tree
        raise ValueError(
            f"family '{fam}' has no paged decode path (apply_paged) — use "
            f"init_inference (v1 KV-cache engine) for this model")
    return build_engine_v2(model, model_cfg, params, config=config, **kwargs)
