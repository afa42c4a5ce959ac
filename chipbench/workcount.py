"""Operations and bytes the algorithm needs, from shapes and true lengths.

Counted on the real tokens only: padded rows, padded columns and pages
past a sequence's length are not work.  A roofline share or an MFU built
on these counts then reads the same work whatever kernel implements it.

A dispatch record (written by the harness around each data-plane call)
is a dict with ``kind`` and:

* ``prefill``: ``lens``, the prompt length of every real row;
* ``continue``: ``starts`` and ``lens`` per real row;
* ``decode``: ``ctxs``, the context length (cached tokens plus the new
  one) of every real lane at every inner step of the dispatch.
"""

from __future__ import annotations

ITEMSIZE = {"bfloat16": 2, "float32": 4}


def _dims(cfg: dict) -> tuple[int, int, int, int]:
    return (cfg["num_hidden_layers"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"])


def decode_attention(cfg: dict, ctxs) -> tuple[float, float]:
    """``(flops, bytes)`` of paged decode attention over every layer for
    one query token per entry of ``ctxs``: QK^T and PV are ``4 Hq hd ctx``
    flops; K and V of ``ctx`` tokens are read, q read and o written."""
    n_layers, hq, hkv, hd = _dims(cfg)
    item = ITEMSIZE[cfg["torch_dtype"]]
    total = sum(ctxs)
    flops = 4.0 * hq * hd * total * n_layers
    nbytes = (2.0 * hkv * hd * total + 2.0 * hq * hd * len(ctxs)) \
        * item * n_layers
    return flops, nbytes


def flash_prefill(cfg: dict, lens) -> tuple[float, float]:
    """``(flops, bytes)`` of causal prefill attention over every layer:
    ``n (n + 1) / 2`` query-key pairs per head of an ``n``-token row at
    ``4 hd`` flops each; q, k, v read and o written once."""
    n_layers, hq, hkv, hd = _dims(cfg)
    item = ITEMSIZE[cfg["torch_dtype"]]
    pairs = sum(n * (n + 1) / 2 for n in lens)
    flops = 4.0 * hq * hd * pairs * n_layers
    nbytes = (2.0 * hq + 2.0 * hkv) * hd * sum(lens) * item * n_layers
    return flops, nbytes


def matmul_params(cfg: dict) -> int:
    """Weights of the layers' matmuls (no embedding, no LM head)."""
    n_layers, hq, hkv, hd = _dims(cfg)
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    return n_layers * (2 * d * hq * hd + 2 * d * hkv * hd + 3 * d * f)


def model_flops(cfg: dict, rec: dict) -> float:
    """Model FLOPs of one dispatch: 2 per matmul weight per real token,
    the LM head once per logits row produced, plus the attention."""
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"]
    body = 2.0 * matmul_params(cfg)
    n_layers, hq, _, hd = _dims(cfg)
    kind = rec["kind"]
    if kind == "prefill":
        lens = rec["lens"]
        attn = flash_prefill(cfg, lens)[0]
        return body * sum(lens) + head * len(lens) + attn
    if kind == "continue":
        pairs = sum(n * s + n * (n + 1) / 2
                    for s, n in zip(rec["starts"], rec["lens"]))
        attn = 4.0 * hq * hd * pairs * n_layers
        return body * sum(rec["lens"]) + head * len(rec["lens"]) + attn
    if kind == "decode":
        ctxs = rec["ctxs"]
        return (body + head) * len(ctxs) + decode_attention(cfg, ctxs)[0]
    raise ValueError(f"unknown dispatch kind {kind!r}")


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """Least time on the chip: the larger of the compute and memory
    bounds (``peak``: ``flops_per_s``, ``bytes_per_s``)."""
    return max(flops / peak["flops_per_s"], nbytes / peak["bytes_per_s"])
