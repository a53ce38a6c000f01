"""Bytes of a CCA layer's MIXING (``models/zaya.py`` ``cca_mix``: the two
convolutions, the q-k mean, the L2 norm, the value shift, the rope and the
tail's read and write), from the configuration's published keys: what
``cca_mix_roofline`` divides by. Counted for the mechanism itself - what it
must read and write once whatever implements it - so the share reads the
same work under the XLA operations of today and under a fused kernel.
Everything is for ONE layer over ONE call.
"""

from __future__ import annotations

from .costs import BF16_BYTES, head_dim


def latent(cfg: dict) -> int:
    """Channels of ``p = [q | k]``: the convolutions' width."""
    return (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) \
        * head_dim(cfg)


def row_numbers(cfg: dict) -> int:
    """Numbers ONE row moves: its ``p`` and its two value halves in; its q,
    k and v (two KV heads) out."""
    d = head_dim(cfg)
    return (latent(cfg) + 2 * d) + (latent(cfg) + 2 * d)


def tail_numbers(cfg: dict) -> int:
    """Numbers of ONE sequence's tail: the last ``cca_time0 + cca_time1 - 2``
    rows of ``p`` and the last row of the shifted value's half."""
    return (cfg["cca_time0"] + cfg["cca_time1"] - 2) * latent(cfg) \
        + head_dim(cfg)


def conv_params(cfg: dict) -> int:
    """Both convolutions' weights and biases: a depthwise tap a channel, then
    a ``[d, d]`` matrix a tap a head."""
    c, d = latent(cfg), head_dim(cfg)
    return (cfg["cca_time0"] * c + c) + (cfg["cca_time1"] * c * d + c)


def call_floor_bytes(cfg: dict, rows: float, tail_rows: float) -> float:
    """Least bytes one layer's mixing moves for a call of ``rows`` rows whose
    tails come from and go back to ``tail_rows`` pool rows: every row's
    operands once in and once out, every tail once in and once out, the
    convolutions' weights once; all bf16."""
    return BF16_BYTES * (rows * row_numbers(cfg)
                         + 2 * tail_rows * tail_numbers(cfg)
                         + conv_params(cfg))
