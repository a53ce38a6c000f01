"""A family's program beside its reference, run AS THE WINDOW RUNS IT: every
call is the engine's mixed call (``models/_paged.py`` ``MixedCall``: a decode
row of every sequence slot and one sequence's prefill chunk in ONE forward)
over pools of the serve role's slots, with other sequences live in the other
slots. One class for every family whose ``apply_paged`` takes the engine's
contract - a family hands over its own module (``module()``, ``build_cfg``,
``serve_role``) and nothing else; no family copies this file.

A probe's life, ``tokens`` all GIVEN (none is sampled), over fresh pools:

1. every NEIGHBOUR slot (all but the judged sequence's and one more) is
   admitted, a tick each: its prompt, one chunk of random tokens of a random
   length, rides a mixed call beside the decode rows of the neighbours
   admitted before it; the slots not yet admitted are idle rows (aimed at
   the trash block and the trash row, as the engine aims them);
2. the JUDGED sequence's first ``n`` tokens go in padded chunks of the
   role's SplitFuse size through its own slot, every neighbour decoding a
   given random token beside each;
3. its last tokens enter ONE A TICK as its slot's decode row, beside the
   neighbours' decode rows and the chunks of a FILLER sequence in the last
   slot (a prompt of ``SPAN_CHUNKS`` chunks, started again from a fresh
   state when it ends): the tick the cell's window is made of.

The judged slot, the filler's, every sequence's blocks (a random permutation
of the pool's, so no table is in order) and every neighbour's tokens come
from the probe's own tokens: the same probe gives the same calls. A wrong
slot's state row, a wrong split or join of the two segments, a table read
in another slot's place or a write through an idle row lands in the judged
rows.
"""

from __future__ import annotations

import dataclasses
import functools

import numpy as np

# of a probe's chunked part, the last so many rows are judged; every call
# reads as many rows of logits (a decode tick its slot's row, repeated)
PROMPT_ROWS = 64
# a sequence that is not the judged one holds at most so many chunks of
# context (a neighbour: its prompt and a token a tick after it)
SPAN_CHUNKS = 4


@functools.lru_cache(maxsize=None)
def mixed_call(family, cfg, dtype: str):
    """One jitted mixed call a family, configuration and precision, for every
    ``MixedProgram`` of a process: ``step [slots]`` the slots' decode tokens,
    ``chunk [t]`` the chunk's, the rest a ``MixedCall``'s fields; the logits
    at ``rows [PROMPT_ROWS]`` of the ``slots + t`` rows, the cache donated.
    (``mixed_call.__wrapped__`` is a jit of its own: a check tool traces one
    with a fault planted.)"""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.inference import MixedCall

    m = family.module()

    def call(params, cache, step, chunk, tables, lens, active, table, ctx,
             n_valid, slot, rows):
        mixed = MixedCall(tables, lens, active, table, ctx, n_valid, slot)
        tokens = jnp.concatenate([step, chunk])[None]
        logits, cache = m.apply_paged(
            cfg, params, tokens, cache, mixed, None,
            valid=mixed.valid(tokens.shape[1]), rows=rows[None],
            compute_dtype=jnp.dtype(dtype))
        return logits[0], cache

    return jax.jit(call, donate_argnums=(1,))


@dataclasses.dataclass
class Book:
    """The host's side of a probe's pools: every slot's block table, length
    and whether it decodes, the blocks not handed out, where the judged and
    the filler sequences sit, and the draw the other sequences' tokens come
    from. ``copy()`` it beside a copy of the pools to run on from the same
    place twice."""
    tables: np.ndarray
    lens: np.ndarray
    active: np.ndarray
    free: list
    judged: int
    filler: int
    rng: np.random.Generator

    def copy(self) -> "Book":
        rng = np.random.default_rng()
        rng.bit_generator.state = self.rng.bit_generator.state
        return Book(self.tables.copy(), self.lens.copy(), self.active.copy(),
                    list(self.free), self.judged, self.filler, rng)


class MixedProgram:
    """``logits`` are ``family.module().apply_paged``'s in the served
    precision (the role's ``weights_dtype``) over the serve role's geometry
    (slots, blocks, SplitFuse chunk) - ``prefill`` (steps 1 and 2 above),
    then ``decode`` (step 3). The two are apart so that a control can give
    the decode ticks ALONE another program (a fault planted in the
    single-token segment: the reference's ``held`` judges the decoded rows by
    themselves). ``limits``: what the configuration holds the logits to
    (``roles.serve.held``). ``role`` is the configuration's serve role (None:
    the configuration file's); ``options`` are laid over the role's
    ``program_options`` (the ``state_dtype`` control)."""

    def __init__(self, family, params, role=None, options=None):
        self.family, self.params, self._role = family, params, role
        self.options, self.call = options or {}, None

    def _setup(self, hf: dict):
        if self.call is not None:
            return
        import jax.numpy as jnp

        role = self._role = self._role or self.family.serve_role(hf)
        self.cfg = self.family.build_cfg(
            hf, **{**role["program_options"], **self.options})
        self.limits = role["held"]
        self.dtype = jnp.dtype(role["weights_dtype"])
        engine = role["engine"]
        self.block = engine["ragged"]["block_size"]
        self.slots = engine["ragged"]["max_tracked_sequences"]
        self.chunk = engine["split_prefill_chunk"]
        self.vocab = hf["vocab_size"]
        self.width = -(-hf["max_position_embeddings"] // self.block)
        # what a sequence beside the judged one may hold, in blocks
        self.span = -(-SPAN_CHUNKS * self.chunk // self.block)
        assert self.slots >= 2, "a judged slot and a filler's"
        self.call = mixed_call(self.family, self.cfg, self.dtype.name)

    # ------------------------------------------------------------------ #
    def _blocks(self, book: Book, slot: int, tokens: int):
        """``slot``'s table holds ``tokens`` tokens."""
        have = int(np.count_nonzero(book.tables[slot]))
        need = -(-tokens // self.block)
        limit = self.width if slot == book.judged else self.span
        assert need <= limit, (slot, tokens, limit)
        for j in range(have, need):
            book.tables[slot, j] = book.free.pop()

    def _tick(self, call, cache, book: Book, slot, tokens, start, end, rows,
              given=None):
        """One mixed call: ``tokens[start:end]`` as ``slot``'s chunk at
        context ``start`` beside a decode row of every active slot, each a
        random token (``given``: the judged slot's); ``rows`` of the call's
        rows are read. Returns (the logits at them, still on the device; the
        cache)."""
        import jax.numpy as jnp

        assert not book.active[slot]
        live = np.flatnonzero(book.active)
        step = np.zeros(self.slots, np.int32)
        step[live] = book.rng.integers(0, self.vocab, len(live))
        if given is not None:
            step[book.judged] = given
        for i in live:
            self._blocks(book, i, book.lens[i] + 1)
        self._blocks(book, slot, end)
        padded = np.zeros(self.chunk, np.int32)
        padded[:end - start] = tokens[start:end]
        i32 = lambda a: jnp.asarray(a, jnp.int32)
        # an idle slot's row: the trash block at length 0, as the engine's
        tables = np.where(book.active[:, None], book.tables, 0)
        lens = np.where(book.active, book.lens, 0)
        out, cache = call(
            self.params, cache, i32(step), i32(padded), i32(tables),
            i32(lens), jnp.asarray(book.active), i32(book.tables[slot]),
            i32(start), i32(end - start), i32(slot), i32(rows))
        book.lens[live] += 1
        book.lens[slot] = end
        return out, cache

    def prefill(self, hf: dict, tokens, n: int):
        """Fresh pools, the neighbours admitted, then the first ``n`` of
        ``tokens`` in chunks through the judged slot: ``(the logits at the
        last min(PROMPT_ROWS, the final chunk's rows) of them, the pools,
        the pools' book)``."""
        self._setup(hf)
        tokens = np.asarray(tokens, np.int32)
        assert 0 < n <= len(tokens) <= self.width * self.block, len(tokens)
        rng = np.random.default_rng(
            [len(tokens), int(tokens[0]), int(tokens[-1]), 0x501A])
        # (one pool shape for every probe: one compile)
        blocks = 1 + self.width + (self.slots - 1) * self.span
        cache = self.family.module().init_paged_cache(
            self.cfg, blocks, self.block, dtype=self.dtype, slots=self.slots)
        order = rng.permutation(self.slots)
        book = Book(np.zeros((self.slots, self.width), np.int32),
                    np.zeros(self.slots, np.int32),
                    np.zeros(self.slots, bool),
                    (1 + rng.permutation(blocks - 1)).tolist(),
                    int(order[0]), int(order[1]), rng)
        ticks = len(order) - 2 + -(-n // self.chunk) + len(tokens) - n
        assert self.chunk + ticks <= self.span * self.block, ticks
        idle = np.zeros(PROMPT_ROWS, np.int32)
        for slot in order[2:]:
            prompt = rng.integers(0, self.vocab,
                                  int(rng.integers(1, self.chunk + 1)))
            _, cache = self._tick(self.call, cache, book, int(slot), prompt,
                                  0, len(prompt), idle)
            book.active[slot] = True
        for start in range(0, n, self.chunk):
            end = min(start + self.chunk, n)
            r = min(PROMPT_ROWS, end - start)
            rows = self.slots + np.clip(
                end - start - r + np.arange(PROMPT_ROWS), 0, None)
            out, cache = self._tick(self.call, cache, book, book.judged,
                                    tokens, start, end, rows)
        return np.asarray(out[:r]), cache, book

    def decode(self, hf: dict, tokens, n: int, cache, book: Book, call=None):
        """``tokens[n:]`` one a tick through the judged slot's decode row
        over the pools ``prefill`` left (they are DONATED; ``book`` is left
        as it was), the filler's chunks beside them: a row of logits each.
        ``call``: another program than this one's for these ticks
        (``mixed_call``'s signature)."""
        self._setup(hf)
        tokens = np.asarray(tokens, np.int32)
        book, call = book.copy(), call or self.call
        book.active[book.judged] = True
        assert book.lens[book.judged] == n, (book.lens, n)
        rows = np.full(PROMPT_ROWS, book.judged, np.int32)
        length = SPAN_CHUNKS * self.chunk
        out, at = [], 0
        for i in range(n, len(tokens)):
            if at == 0:     # the filler's next prompt, from a fresh state
                filler = book.rng.integers(0, self.vocab, length)
            row, cache = self._tick(call, cache, book, book.filler, filler,
                                    at, at + self.chunk, rows,
                                    given=tokens[i])
            out.append(row[:1])
            at = (at + self.chunk) % length
        del cache
        return np.concatenate([np.asarray(r) for r in out])

    def logits(self, hf: dict, tokens, decode: int):
        """``[p + decode, vocab]``: the logits at the last ``p + decode``
        positions of ``tokens`` - ``prefill``'s rows, then a row a decode
        tick."""
        n = len(tokens) - decode
        rows, cache, book = self.prefill(hf, tokens, n)
        return np.concatenate(
            [rows, self.decode(hf, tokens, n, cache, book)])
