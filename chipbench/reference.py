"""Plain reference forward pass of a dense GQA decoder, and its int8 control.

It imports nothing of the program under test and takes nothing the
program made: the weights come again from ``weights.make`` and the seed.
It follows the published layer equations of the Qwen2 / Llama family:

    h = x + Wo . attn(RoPE(Wq n1(x) + bq), RoPE(Wk n1(x) + bk), Wv n1(x) + bv)
    y = h + Wd (silu(Wg n2(h)) * Wu n2(h))
    logits = head . n_f(y_L)

with RMSNorm ``n(x) = x / sqrt(mean(x^2) + eps) * scale``, rotate-half
RoPE at ``rope_theta``, causal attention scaled by ``head_dim ** -0.5``,
query head ``h`` reading KV head ``h // (Hq / Hkv)``.  Everything is
float32 at ``Precision.HIGHEST``; weights are upcast from the served
dtype.  It runs one whole sequence per row, teacher-forced on the served
tokens, layer by layer and in blocks of query rows, so that it fits on
one chip once the program's state is freed.

A control is the same pass with every weight matmul, the LM head's too,
in a precision below the bf16 the configurations state: ``"int8"``
(per-row activation and per-column weight absmax scales, int32
accumulation) or ``"fp8"`` (e4m3 with the same scaling, float32
accumulation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
#: query rows per attention / MLP block
BLOCK = 256


def _mm(x: jax.Array, w: jax.Array) -> jax.Array:
    return jnp.matmul(x, w.astype(jnp.float32), precision=HIGHEST)


def _mm_int8(x: jax.Array, w: jax.Array) -> jax.Array:
    xs = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / 127
    wf = w.astype(jnp.float32)
    ws = jnp.maximum(jnp.max(jnp.abs(wf), axis=0, keepdims=True), 1e-30) / 127
    xq = jnp.round(x / xs).astype(jnp.int8)
    wq = jnp.round(wf / ws).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    return acc.astype(jnp.float32) * xs * ws


def _mm_fp8(x: jax.Array, w: jax.Array) -> jax.Array:
    fp8, top = jnp.float8_e4m3fn, 448.0
    xs = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-30) / top
    wf = w.astype(jnp.float32)
    ws = jnp.maximum(jnp.max(jnp.abs(wf), axis=0, keepdims=True), 1e-30) / top
    xq = (x / xs).astype(fp8).astype(jnp.float32)
    wq = (wf / ws).astype(fp8).astype(jnp.float32)
    return jnp.matmul(xq, wq, precision=HIGHEST) * xs * ws


#: the matmul of each precision: the reference, and the two controls
MATMUL = {"f32": _mm, "int8": _mm_int8, "fp8": _mm_fp8}


def _rmsnorm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x: jax.Array, pos: jax.Array, theta: float) -> jax.Array:
    """x [S, T, H, hd], pos [T]: rotate-half RoPE."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos.astype(jnp.float32)[:, None] * inv              # [T, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _static(cfg: dict) -> tuple:
    return (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], float(cfg["rms_norm_eps"]),
            float(cfg["rope_theta"]), bool(cfg["attention_bias"]))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(st: tuple, mode: str, lw: dict, x: jax.Array) -> jax.Array:
    hq, hkv, hd, eps, theta, bias = st
    mm = MATMUL[mode]
    s, t, d = x.shape
    g = hq // hkv
    pos = jnp.arange(t)
    h = _rmsnorm(x, lw["ln1"], eps)
    k = mm(h, lw["wk"])
    v = mm(h, lw["wv"])
    if bias:
        k = k + lw["bk"].astype(jnp.float32)
        v = v + lw["bv"].astype(jnp.float32)
    k = _rope(k.reshape(s, t, hkv, hd), pos, theta)
    v = v.reshape(s, t, hkv, hd)

    def block(i):
        start = i * BLOCK
        xs = jax.lax.dynamic_slice_in_dim(x, start, BLOCK, axis=1)
        hs = jax.lax.dynamic_slice_in_dim(h, start, BLOCK, axis=1)
        q = mm(hs, lw["wq"])
        if bias:
            q = q + lw["bq"].astype(jnp.float32)
        qpos = start + jnp.arange(BLOCK)
        q = _rope(q.reshape(s, BLOCK, hq, hd), qpos, theta)
        q = q.reshape(s, BLOCK, hkv, g, hd)
        sc = jnp.einsum("sqhgd,skhd->shgqk", q, k,
                        precision=HIGHEST) * hd ** -0.5
        mask = qpos[:, None] >= pos[None, :]
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("shgqk,skhd->sqhgd", p, v, precision=HIGHEST)
        xs = xs + mm(o.reshape(s, BLOCK, hq * hd), lw["wo"])
        hn = _rmsnorm(xs, lw["ln2"], eps)
        ff = jax.nn.silu(mm(hn, lw["w_gate"])) * mm(hn, lw["w_up"])
        return xs + mm(ff, lw["w_down"])

    out = jax.lax.map(block, jnp.arange(t // BLOCK))        # [nb, S, B, D]
    return out.transpose(1, 0, 2, 3).reshape(s, t, d)


@jax.jit
def _embed(embed: jax.Array, tokens: jax.Array) -> jax.Array:
    return embed[tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _gaps(eps: float, control: str | None, ln_f: jax.Array,
          head: jax.Array, h_ref: jax.Array, h_ctl: jax.Array,
          targets: jax.Array):
    """Per position: the reference's best logit minus its logit of the
    target token, and (``control``) minus its logit of the token the
    control puts first."""
    s, t, _ = h_ref.shape
    yr = _rmsnorm(h_ref, ln_f, eps)
    yc = _rmsnorm(h_ctl, ln_f, eps)

    def block(i):
        start = i * BLOCK
        lr = _mm(jax.lax.dynamic_slice_in_dim(yr, start, BLOCK, 1), head)
        best = lr.max(axis=-1)
        tg = jax.lax.dynamic_slice_in_dim(targets, start, BLOCK, 1)
        served = best - jnp.take_along_axis(lr, tg[..., None], -1)[..., 0]
        if control:
            lc = MATMUL[control](
                jax.lax.dynamic_slice_in_dim(yc, start, BLOCK, 1), head)
            tc = jnp.argmax(lc, axis=-1)
            ctl = best - jnp.take_along_axis(lr, tc[..., None], -1)[..., 0]
        else:
            ctl = jnp.zeros_like(served)
        return served, ctl

    served, ctl = jax.lax.map(block, jnp.arange(t // BLOCK))   # [nb, S, B]
    fix = lambda a: a.transpose(1, 0, 2).reshape(s, t)  # noqa: E731
    return fix(served), fix(ctl)


def _hidden(cfg: dict, w: dict, tokens: jax.Array, mode: str) -> jax.Array:
    st = _static(cfg)
    x = _embed(w["embed"], tokens)
    for layer in range(cfg["num_hidden_layers"]):
        lw = {k: v[layer] for k, v in w.items()
              if k not in ("embed", "ln_f", "head")}
        x = _layer(st, mode, lw, x)
    return x


def pack(seqs: list[tuple[np.ndarray, np.ndarray]], length: int):
    """``(tokens [S, T], targets [S, T], valid [S, T])`` for teacher-forced
    rows ``prompt + served``: position ``t`` predicts ``tokens[t + 1]``,
    and is valid where that token was served."""
    rows = len(seqs)
    tokens = np.zeros((rows, length), np.int32)
    targets = np.zeros((rows, length), np.int32)
    valid = np.zeros((rows, length), bool)
    for i, (prompt, served) in enumerate(seqs):
        seq = np.concatenate([prompt, served]).astype(np.int32)
        if len(seq) > length:
            raise ValueError(f"row {i}: {len(seq)} tokens > {length}")
        tokens[i, :len(seq)] = seq
        n = len(prompt)
        targets[i, n - 1:len(seq) - 1] = served
        valid[i, n - 1:len(seq) - 1] = True
    return tokens, targets, valid


def logit_gaps(cfg: dict, w: dict, seqs: list[tuple[np.ndarray, np.ndarray]],
               length: int, *, control: str | None = None,
               cache: dict | None = None):
    """For each served token of ``seqs``: the reference's best logit minus
    its logit of that token (``served``), and at the same positions the
    gap of the first choice of the control ``"int8"`` or ``"fp8"``
    (``ctl``; zeros without a control).  ``length`` (a multiple of :data:`BLOCK`) is the padded
    row length, fixed per cell so one compile serves every run.  ``cache``
    (a dict) keeps the float32 hidden states for later calls on the same
    ``seqs`` and weights."""
    if length % BLOCK:
        raise ValueError(f"length {length} is not a multiple of {BLOCK}")
    tokens, targets, valid = pack(seqs, length)
    tok = jnp.asarray(tokens)
    if cache is not None and "f32" in cache:
        h_ref = cache["f32"]
    else:
        h_ref = _hidden(cfg, w, tok, "f32")
        if cache is not None:
            cache["f32"] = h_ref
    h_ctl = _hidden(cfg, w, tok, control) if control else h_ref
    served, ctl = _gaps(float(cfg["rms_norm_eps"]), control, w["ln_f"],
                        w["head"], h_ref, h_ctl, jnp.asarray(targets))
    return np.asarray(served)[valid], np.asarray(ctl)[valid]
