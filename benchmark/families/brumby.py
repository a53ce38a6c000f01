"""The ``brumby`` family: Brumby-14B-Base's published keys ->
``deepspeed_tpu.models.brumby`` (Qwen3's decoder with power retention in the
attention's place, over a per-slot state pool and NO block pool), and the
parameter tree -> the plain reference's weights, one layer at a time. The
program's module is loaded when a cell asks for it: no other family's set-up
pays for it.

The configuration's random weights are the program's own ``init`` (fan-in
scaled normals, norm weights of one, the gate's ``[hidden, kv heads]``
projection among them): no rule is laid over it. What that leaves unlike a
trained model is said in the configuration's ``assumed`` (``weights``): a
bias-less gate of a random projection forgets half its state a token.
"""

from __future__ import annotations

import dataclasses
import functools
import os

import numpy as np

from .cohere2_moe import rounded  # noqa: F401  (the precision control)

REFERENCE = "brumby"
CONFIG_FILE = "brumby-14b-base.json"


def module():
    try:
        from deepspeed_tpu.models import brumby
    except ImportError:
        from benchmark.harness.manifest import ManifestError

        raise ManifestError(
            "this program has no models/brumby.py: it cannot run the "
            "brumby family") from None
    return brumby


def build_cfg(hf: dict, **program_options):
    """Every published size from the configuration file. What the program
    does not have is refused, not dropped."""
    m = module()
    for key in ("attention_bias", "tie_word_embeddings", "sliding_window",
                "use_sliding_window", "rope_scaling"):
        if hf.get(key):
            raise ValueError(f"models/brumby.py has no {key}")
    if hf["hidden_act"] != "silu":
        raise ValueError("the configuration is not one models/brumby.py "
                         "runs as published")
    return dataclasses.replace(
        m.BrumbyConfig(),
        vocab_size=hf["vocab_size"], hidden_size=hf["hidden_size"],
        intermediate_size=hf["intermediate_size"],
        num_layers=hf["num_hidden_layers"],
        num_heads=hf["num_attention_heads"],
        num_kv_heads=hf["num_key_value_heads"], head_dim=hf["head_dim"],
        max_seq_len=hf["max_position_embeddings"],
        rope_theta=float(hf["rope_theta"]), rms_norm_eps=hf["rms_norm_eps"],
        **program_options)


class Weights:
    """The program's stacked parameter tree, read one layer at a time under
    the reference's names. ``program`` is the program these weights are
    served by, for the reference's comparison beyond the served tokens
    (``reference/brumby.py`` ``logits_and_margin``)."""

    _NAMES = {"attn_norm": "attn_norm", "q": "wq", "k": "wk", "v": "wv",
              "o": "wo", "g": "wg", "q_norm": "q_norm", "k_norm": "k_norm",
              "ffn_norm": "mlp_norm", "gate": "w_gate", "up": "w_up",
              "down": "w_down"}

    def __init__(self, params, role=None):
        self._layers = params["layers"]
        self.embed = params["embed"]
        self.final_norm = params["final_norm"]
        self.head = params["lm_head"]           # [hidden, vocab]
        self.program = Program(params, role)

    def layer(self, i: int) -> dict:
        return {name: self._layers[leaf][i]
                for name, leaf in self._NAMES.items()}


def serve_role(hf: dict) -> dict:
    """The serve role of this family's configuration file, at the rehearsal's
    sizes where ``hf`` has the rehearsal's widths."""
    from benchmark.harness import manifest

    data = manifest.load_json(os.path.join(manifest.BENCH_DIR, "configs",
                                           CONFIG_FILE))
    role = data["roles"]["serve"]
    reh = data["rehearsal"]
    if hf["hidden_size"] == reh["published"]["hidden_size"]:
        role = {**role,
                "engine": manifest.merge(role["engine"], reh["serve_engine"]),
                "held": manifest.merge(role["held"], reh["serve_held"])}
    return role


# of a probe's chunked part, the last so many rows are judged
PROMPT_ROWS = 64


@functools.lru_cache(maxsize=None)
def paged_call(cfg, dtype: str):
    """One jitted ``apply_paged`` a configuration and precision, for every
    ``Program`` of a process: the logits of the call's last ``min(
    PROMPT_ROWS, width)`` real rows (a call with fewer real rows repeats its
    first), the cache donated. The sequence is slot 0; no block table is
    read. (``paged_call.__wrapped__`` is a jit of its own:
    ``tools/brumby_check.py`` traces one with a fault planted.)"""
    import jax
    import jax.numpy as jnp

    m = module()

    def call(params, cache, tokens, ctx, n_valid):
        width = tokens.shape[1]
        r = min(PROMPT_ROWS, width)
        valid = jnp.arange(width)[None] < n_valid
        rows = jnp.clip(n_valid - r + jnp.arange(r), 0)[None]
        logits, cache = m.apply_paged(
            cfg, params, tokens, cache, jnp.zeros((1, 1), jnp.int32), ctx,
            valid=valid, rows=rows, compute_dtype=jnp.dtype(dtype))
        return logits[0], cache

    return jax.jit(call, donate_argnums=(1,))


class Program:
    """The program beside its reference, on ONE sequence with a state pool of
    its own (one slot): ``logits`` are ``apply_paged``'s in the served
    precision (the role's ``weights_dtype``) - ``prefill``, the sequence in
    padded chunks of the SplitFuse size through ``retention_chunk``, then
    ``decode``, its last tokens one at a time through
    ``retention_decode_update``. The two are apart so that a control can give
    the single-token calls ALONE another program (``tools/brumby_check.py``:
    ``reference/brumby.py`` ``held`` judges the decoded rows by themselves).
    ``limits``: what the configuration holds the logits to
    (``roles.serve.held``). ``role`` is the configuration's serve role (None:
    the configuration file's); ``weights`` names a type the weights are
    rounded to first (the precision control); ``options`` are laid over the
    role's ``program_options`` (the ``state_dtype`` control)."""

    def __init__(self, params, role=None, weights=None, options=None):
        self.params, self._role, self.call = params, role, None
        self.weights, self.options = weights, options or {}

    def _setup(self, hf: dict):
        if self.call is not None:
            return
        import jax
        import jax.numpy as jnp

        role = self._role = self._role or serve_role(hf)
        self.cfg = build_cfg(hf, **{**role["program_options"],
                                    **self.options})
        self.limits = role["held"]
        self.dtype = jnp.dtype(role["weights_dtype"])
        self.chunk = role["engine"]["split_prefill_chunk"]
        if self.weights is not None:
            self.params = jax.tree.map(
                lambda p: rounded(p, self.weights), self.params)
        self.call = paged_call(self.cfg, self.dtype.name)

    def _run(self, call, tokens, calls, cache):
        """``calls`` (start, end, width) in order over ``cache``: ``(the
        last call's rows and every single-token call's, the cache)``."""
        import jax.numpy as jnp

        rows = []
        for start, end, width in calls:
            padded = np.zeros((1, width), np.int32)
            padded[0, :end - start] = tokens[start:end]
            row, cache = call(
                self.params, cache, jnp.asarray(padded),
                jnp.asarray([start], jnp.int32),
                jnp.asarray(end - start, jnp.int32))
            if width == 1 or (start, end, width) == calls[-1]:
                rows.append(np.asarray(row)[-min(end - start, len(row)):])
        return np.concatenate(rows), cache

    def prefill(self, hf: dict, tokens, n: int):
        """The first ``n`` of ``tokens`` in chunks, over a fresh pool: ``(the
        logits at the last min(PROMPT_ROWS, the final chunk's rows) of them,
        the pool)``."""
        self._setup(hf)
        assert 0 < n <= len(tokens), len(tokens)
        cache = module().init_paged_cache(self.cfg, 0, 0, slots=1)
        return self._run(
            self.call, np.asarray(tokens, np.int32),
            [(a, min(a + self.chunk, n), self.chunk)
             for a in range(0, n, self.chunk)], cache)

    def decode(self, hf: dict, tokens, n: int, cache, call=None):
        """``tokens[n:]`` one a call over the pool ``prefill`` left (it is
        DONATED): a row of logits each. ``call``: another program than this
        one's for them (``paged_call``'s signature)."""
        self._setup(hf)
        rows, cache = self._run(
            call or self.call, np.asarray(tokens, np.int32),
            [(i, i + 1, 1) for i in range(n, len(tokens))], cache)
        del cache
        return rows

    def logits(self, hf: dict, tokens, decode: int):
        """``[p + decode, vocab]``: the logits at the last ``p + decode``
        positions of ``tokens`` - ``prefill``'s rows, then a row a
        single-token call (every token is GIVEN: none is sampled)."""
        n = len(tokens) - decode
        rows, cache = self.prefill(hf, tokens, n)
        return np.concatenate([rows, self.decode(hf, tokens, n, cache)])
