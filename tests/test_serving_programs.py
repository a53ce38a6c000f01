"""The serving engine's programs, to the letter.

``inference/engine_v2.py`` builds four forward programs (prefill, decode, a
prompt chunk inside a step's decode - ISSUE 32's ``decode_chunk``, since
ISSUE 46 the one chunk program -, speculative verify) in the variants the
dispatch sites choose between, and five families route their paged pools through
``models/_paged.scan_layers``. A PR that reshapes that code without meaning
to change a program must leave every jaxpr here as it was. The hashes below
are ISSUE 29's, re-taken one for one on its finished tree: that PR changed
every paged program on purpose (the pools became the layer scan's carry,
written in place and indexed by layer inside the kernels), so what it was
held to instead is the served tokens - ``PARENT_TOKENS``, what the engines
of the commit before it (72e45a1) served greedily. ``str(jaxpr)`` carries no
scope names, so the ``kv_write`` scope that ``scan_layers`` gives gpt, falcon
and exaone4 does not show here; an operation added or moved does.

The last tests count what ONE ``step()`` does on the host before its
program runs (uploads and keys made inside ``engine_v2``: the dispatch
path's cost is the serve cells' ``serve_idle_dispatch_share``), and what a
``launch()`` reads of the device: nothing (ISSUE 35, one program in flight).
"""

import hashlib
import re

import jax
import jax.numpy as jnp
import pytest

from deepspeed_tpu.comm import mesh as mesh_lib
from deepspeed_tpu.inference import SamplingParams, build_engine_v2
from deepspeed_tpu.inference import engine_v2 as engine_mod
from deepspeed_tpu.models import exaone4, falcon, gpt, llama

SLOTS, BLOCK, PAD_T, K, KP1 = 4, 4, 8, 4, 4
STOCHASTIC = SamplingParams(temperature=0.7, top_k=5, top_p=0.9)


def _text(fn, *args) -> str:
    return re.sub(r"0x[0-9a-f]+", "0x", str(jax.make_jaxpr(fn)(*args)))


def _hash(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _engine(**extra):
    cfg = llama.LlamaConfig.tiny(max_seq_len=64)
    mesh_lib.set_mesh(None)
    return build_engine_v2(
        llama, cfg, llama.init(cfg, jax.random.PRNGKey(0)),
        config=dict({"prefill_bucket": PAD_T,
                     "ragged": {"max_tracked_sequences": SLOTS,
                                "max_ragged_batch_size": SLOTS,
                                "memory_config_blocks": 32,
                                "block_size": BLOCK}}, **extra))


@pytest.fixture(scope="module")
def engines():
    return {"bf16": _engine(),
            "int8": _engine(kv_quant={"enabled": True, "group_size": 8})}


def _shapes(tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                        tree)


def _args(eng):
    """Abstract arguments of the programs, by the names the engine's calls
    give them: ``n`` rows of a prefill, one chunk, all ``SLOTS`` of a decode
    or a verify."""
    s = jax.ShapeDtypeStruct
    i32, f32 = jnp.int32, jnp.float32
    width = eng._slot_tables.shape[1]

    def rows(n):
        return (s((n,), f32), s((n,), i32), s((n,), f32), s((n,), bool))

    head = (_shapes(eng.params), _shapes(eng.cache))
    key = s((2,), jnp.uint32)
    prefill = head + (s((2, PAD_T), i32), s((2,), i32), s((2, width), i32))
    slots = (s((SLOTS,), i32), s((SLOTS, width), i32), s((SLOTS,), bool))
    # the newest launched result [slots + 1] (a device array: one program in
    # flight, ISSUE 35), then the seat row and the slots
    decode = head + (s((SLOTS + 1,), i32), s((SLOTS,), i32)) + slots + (key,)
    return {
        "prefill": prefill + (key, s((2,), i32)),
        "prefill_ctx": prefill + (s((2,), i32), key, s((2,), i32)),
        "decode": decode,
        # the slots as decode takes them, then the chunk, its real tokens,
        # its context offset and its block table, the key and the uid
        "decode_chunk": decode[:-1] + (
            s((1, PAD_T), i32), s((), i32), s((), i32), s((width,), i32),
            key, s((), i32)),
        "verify": head + (s((SLOTS, KP1), i32),) + slots + (
            s((SLOTS,), i32), s((SLOTS, KP1 - 1), i32), key,
            s((SLOTS,), i32)) + rows(SLOTS),
        "rows2": rows(2), "rows": rows(SLOTS), "rows+1": rows(SLOTS + 1)}


# name -> (engine, its builder's call, the arguments' names in ``_args``)
PROGRAMS = {
    "prefill.greedy": ("bf16", lambda e: e._prefill_fn(PAD_T, 2, False, False),
                       ("prefill",)),
    "prefill.rows": ("bf16", lambda e: e._prefill_fn(PAD_T, 2, False, True),
                     ("prefill", "rows2")),
    "prefill_ctx.greedy": ("bf16",
                           lambda e: e._prefill_fn(PAD_T, 2, True, False),
                           ("prefill_ctx",)),
    "prefill_ctx.rows": ("bf16",
                         lambda e: e._prefill_fn(PAD_T, 2, True, True),
                         ("prefill_ctx", "rows2")),
    "decode.greedy": ("bf16", lambda e: e._decode_fn(1, False), ("decode",)),
    "decode.rows": ("bf16", lambda e: e._decode_fn(1, True),
                    ("decode", "rows")),
    "decode_many.greedy": ("bf16", lambda e: e._decode_fn(K, False),
                           ("decode",)),
    "decode_many.rows": ("bf16", lambda e: e._decode_fn(K, True),
                         ("decode", "rows")),
    "spec_verify": ("bf16", lambda e: e._verify_fn(KP1), ("verify",)),
    "decode.greedy.int8": ("int8", lambda e: e._decode_fn(1, False),
                           ("decode",)),
    "decode_chunk.greedy": ("bf16", lambda e: e._decode_chunk_fn(
        PAD_T, False), ("decode_chunk",)),
    "decode_chunk.rows": ("bf16", lambda e: e._decode_chunk_fn(PAD_T, True),
                          ("decode_chunk", "rows+1")),
    "decode_chunk.greedy.int8": ("int8", lambda e: e._decode_chunk_fn(
        PAD_T, False), ("decode_chunk",)),
}


def program_text(engines, name: str) -> str:
    which, build, arg_names = PROGRAMS[name]
    eng = engines[which]
    args = _args(eng)
    return _text(build(eng), *sum((args[a] for a in arg_names), ()))


PAGED_FAMILIES = {
    "gpt": lambda: (gpt, gpt.GPTConfig.tiny()),
    "falcon": lambda: (falcon, falcon.FalconConfig.tiny()),
    "exaone4": lambda: (exaone4, exaone4.Exaone4Config.tiny()),
}


def paged_text(family: str, t: int) -> str:
    """``apply_paged`` of a family over ``t`` tokens a row, on shapes."""
    module, cfg = PAGED_FAMILIES[family]()
    s = jax.ShapeDtypeStruct
    params = jax.eval_shape(lambda k: module.init(cfg, k),
                            jax.random.PRNGKey(0))
    paged = jax.eval_shape(lambda: module.init_paged_cache(cfg, 8, BLOCK))
    return _text(lambda p, x, c, b, n: module.apply_paged(cfg, p, x, c, b, n),
                 params, s((2, t), jnp.int32), paged, s((2, 4), jnp.int32),
                 s((2,), jnp.int32))


# ISSUE 29's: taken on its finished tree under this directory's conftest (the
# lines before it were f552895's); the three ``decode_chunk`` lines were ISSUE
# 32's, which added the program. ISSUE 35 replaced the eight ``decode*`` lines
# on purpose: those programs take the newest launched token result and build
# their slots' token row from it on the device (``engine_v2._own_tokens``), and
# ``decode`` returns its tokens in that result's one shape, ``[slots + 1]``;
# what they serve is held by ``PARENT_TOKENS`` below. ISSUE 44 replaced the
# four ``prefill*``, the two final ``chunk_prefill`` and the three
# ``decode_chunk`` lines on purpose: those programs hand the family the rows
# they read (``rows=``) and the head scores those alone; the programs that
# read every row or none (``decode*``, ``spec_verify``, a mid
# ``chunk_prefill``) and the families' ``rows=None`` forwards kept theirs,
# and ``PARENT_TOKENS`` holds what all of them serve. ISSUE 46 took the four
# ``chunk_prefill`` lines out with the program; no other line was re-taken
# (gpt, falcon and exaone4 now take their positions from
# ``_paged.row_positions``: the same operations on a ``[b, t]`` call). A PR
# that means to change one of these programs replaces its line.
PARENT_HASHES = {
    "decode.greedy": "98acf8881fef6b9a",
    "decode_chunk.greedy": "4ba9752be7c30d72",
    "decode_chunk.greedy.int8": "884492885c6ea0c5",
    "decode_chunk.rows": "435b87fe6292d131",
    "decode.greedy.int8": "a979f095099b879b",
    "decode.rows": "741da01e1d860528",
    "decode_many.greedy": "db20d44910b2f3f1",
    "decode_many.rows": "db38afbd2bb603d5",
    "exaone4.apply_paged.t1": "9052b4becd0e33b1",
    "exaone4.apply_paged.t8": "b6ec6f09c8e127b0",
    "falcon.apply_paged.t1": "48f438229bd392fb",
    "falcon.apply_paged.t8": "34a9462d4be1d3b6",
    "gpt.apply_paged.t1": "ee3a1123c85ea42a",
    "gpt.apply_paged.t8": "8a8f509981f5c0ca",
    # re-taken by ISSUE 48: a one-shot prefill's zero context is a numpy
    # constant of the program (a literal where it was a ``broadcast_in_dim``),
    # so that the ops can read the walk's static reach; the tokens stand
    "prefill.greedy": "f44345a1773be2e5",
    "prefill.rows": "3def0f59872148a6",
    "prefill_ctx.greedy": "c9962616fcb0d7e9",
    "prefill_ctx.rows": "77ec64d492592b6a",
    "spec_verify": "5a2b0e419537fdc2",
}


@pytest.mark.parametrize("name", sorted(PROGRAMS))
def test_the_engines_program_is_the_parents(engines, name):
    assert _hash(program_text(engines, name)) == PARENT_HASHES[name]


@pytest.mark.parametrize("t", [8, 1])
@pytest.mark.parametrize("family", sorted(PAGED_FAMILIES))
def test_the_familys_paged_forward_is_the_parents(family, t):
    assert _hash(paged_text(family, t)) == \
        PARENT_HASHES[f"{family}.apply_paged.t{t}"]


# what the parent of ISSUE 29 (72e45a1) served in ``_serve``, greedily, through
# every program: the check that stood in for "same jaxpr" in that PR
PARENT_TOKENS = {
    "bf16": {
        "1": [34, 197, 163, 113, 101, 85, 28, 181, 91],
        "2": [156, 147, 156, 147, 135, 186, 156, 147, 164],
        "3": [13, 227, 116, 71, 105, 152, 152],
    },
    "int8": {
        "1": [34, 197, 163, 113, 101, 85, 28, 181, 91],
        "2": [156, 147, 156, 147, 135, 186, 156, 147, 164],
        "3": [13, 227, 116, 71, 105, 152, 152],
    },
    "spec": {
        "1": [34, 197, 163, 113, 101],
        "2": [156, 147, 156, 147, 135, 186],
        "3": [13, 227, 116],
    },
}


def _serve(eng):
    """A burst prefill, a split prompt's chunks beside live decodes, single
    ticks (drafted and verified where the engine speculates), a fused
    quantum: ``{uid: tokens}``."""
    import numpy as np

    rng = np.random.RandomState(7)
    out = {}
    eng.put(1, rng.randint(1, 200, 5).tolist())
    eng.put(2, rng.randint(1, 200, 11).tolist())
    eng.put_split(3, rng.randint(1, 200, 21).tolist())
    for _ in range(5):
        for uid, t in eng.step().items():
            out.setdefault(uid, []).extend(t if isinstance(t, list) else [t])
    if not eng._spec_on:
        for uid, ts in eng.step_many(4).items():
            out.setdefault(uid, []).extend(ts)
    return {str(uid): [int(t) for t in ts] for uid, ts in out.items()}


@pytest.mark.parametrize("mode", sorted(PARENT_TOKENS))
def test_the_engine_serves_the_parents_tokens(mode):
    extra = {"bf16": {}, "int8": {"kv_quant": {"enabled": True,
                                               "group_size": 8}},
             "spec": {"speculative": {"enabled": True,
                                      "max_draft_tokens": 3}}}[mode]
    assert _serve(_engine(**extra)) == PARENT_TOKENS[mode]


def test_the_programs_keep_the_names_the_benchmark_reads(engines):
    """``benchmark/readers`` find ``jit_decode`` by the inner functions'
    names, which the pinned text carries; the ``^jit_decode`` pattern also
    finds ``jit_decode_chunk``, a step with a prompt chunk. No program is
    named ``chunk_prefill`` (ISSUE 46): ``^jit_chunk_prefill`` finds
    nothing."""
    for name, want in (("decode.greedy", "name=decode"),
                       ("decode_many.rows", "name=decode_many"),
                       ("decode_chunk.greedy", "name=decode_chunk"),
                       ("decode_chunk.rows", "name=decode_chunk"),
                       ("prefill_ctx.rows", "name=prefill"),
                       ("spec_verify", "name=verify")):
        assert re.search(want + r"\b", program_text(engines, name)), name


# --- what one step() does on the host before its program runs -------------- #
class _Counted:
    """Stands in for a module inside ``engine_v2``: the attribute at the end
    of ``path`` counts its calls, everything else is the module's own."""

    def __init__(self, target, path, counts):
        self._target, self._path, self._counts = target, path, counts

    def __getattr__(self, name):
        value = getattr(self._target, name)
        if name != self._path[0]:
            return value
        if len(self._path) > 1:
            return _Counted(value, self._path[1:], self._counts)

        def counted(*args, **kwargs):
            self._counts[name] += 1
            return value(*args, **kwargs)

        return counted


# What a step uploads and the keys it makes, since ISSUE 35 (one program in
# flight). The slots' LAST TOKENS no longer go up: they stay on the device, in
# the result of the program launched before, which goes into the next program
# as the device array it is. What goes up in their place is the seat row - the
# host's value where the host seated a slot since (a one-shot ``put``, a
# ``resume``), else a code that says where in that result the token lies -, so
# the count is the parent's (f552895 / ISSUE 32): the four slot arrays, a key,
# in rows mode the four sampling arrays; ``mixed``: the slot arrays, the
# chunk's four and the uid with ONE key. Shaving these is what ROADMAP Queue A1
# has left, for the ticks that cannot overlap.
STEP_HOST_OPS = {"greedy": {"asarray": 4, "PRNGKey": 1},
                 "rows": {"asarray": 8, "PRNGKey": 1},
                 "mixed": {"asarray": 9, "PRNGKey": 1}}


def _warmed(mode):
    eng = _engine()
    sp = STOCHASTIC if mode == "rows" else SamplingParams(greedy=True)
    eng.put(1, list(range(5)), sp)
    eng.put(2, list(range(7)))
    if mode == "mixed":
        eng.put_split(3, list(range(29)))        # four chunks of 8
    eng.step()                                   # warm: the program exists
    return eng


@pytest.mark.parametrize("mode", sorted(STEP_HOST_OPS))
def test_a_step_uploads_and_makes_keys_as_the_table_says(monkeypatch, mode):
    eng = _warmed(mode)
    counts = {"asarray": 0, "PRNGKey": 0}
    monkeypatch.setattr(engine_mod, "jnp",
                        _Counted(jnp, ("asarray",), counts))
    monkeypatch.setattr(engine_mod, "jax",
                        _Counted(jax, ("random", "PRNGKey"), counts))
    out = eng.step(seed=1)
    assert sorted(out) == [1, 2]
    assert counts == STEP_HOST_OPS[mode]
    assert eng.mixed_steps == (2 if mode == "mixed" else 0)
    assert eng.overlapped_steps == 0             # step() collects at once


@pytest.mark.parametrize("mode", sorted(STEP_HOST_OPS))
def test_a_launch_reads_nothing_of_the_device(monkeypatch, mode):
    """Between a launch and the next launch the host reads no program
    result (``np.asarray`` inside ``engine_v2`` is the one way it does)
    unless something asked for a token's value: ``collect`` reads ONE
    program, the one launched before the newest; a ``park`` reads what is in
    flight first. The uploads stay the table's."""
    import numpy as np

    eng = _warmed(mode)
    counts = {"asarray": 0, "PRNGKey": 0}
    reads = {"asarray": 0}
    monkeypatch.setattr(engine_mod, "jnp",
                        _Counted(jnp, ("asarray",), counts))
    monkeypatch.setattr(engine_mod, "jax",
                        _Counted(jax, ("random", "PRNGKey"), counts))
    monkeypatch.setattr(engine_mod, "np", _Counted(np, ("asarray",), reads))
    got = {}
    assert eng.launch(seed=1) == 1 and reads["asarray"] == 0
    for seed in (2, 3):
        assert eng.launch(seed=seed) == 1        # the program before unread
        assert reads["asarray"] == seed - 2 and eng.in_flight == 2
        for uid, toks in eng.collect(ahead=1).items():
            got.setdefault(uid, []).extend(toks)
        assert reads["asarray"] == seed - 1 and eng.in_flight == 1
    assert counts == {k: 3 * v for k, v in STEP_HOST_OPS[mode].items()}
    assert eng.overlapped_steps == 2
    assert eng.tokens_uncollected() == dict.fromkeys(
        [1, 2] + [3] * (mode == "mixed"), 1)     # 3's first token: in flight
    parked = eng.park(1)                         # needs the values: drains
    assert reads["asarray"] == 3 and eng.in_flight == 0
    for uid, toks in eng.collect().items():
        got.setdefault(uid, []).extend(toks)
    # every token once, in order: what three synchronous steps serve
    ref = _warmed(mode)
    want = {}
    for seed in (1, 2, 3):
        for uid, tok in ref.step(seed=seed).items():
            want.setdefault(uid, []).append(tok)
    assert got == want
    assert parked["generated"] == ref.state.seqs[1].generated
    assert eng.state.seqs[2].generated == ref.state.seqs[2].generated
