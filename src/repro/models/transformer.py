"""Decoder-only transformer LM (dense / MoE / VLM / audio families).

Design (DESIGN.md §3):
  * params are dict pytrees; the repeated block's params are STACKED along a
    leading layer axis and the forward pass is a ``lax.scan`` — HLO size is
    O(1) in depth, which keeps 95-layer x 512-device dry-runs compilable and
    matches production frameworks;
  * training uses blockwise causal attention (flash kernel or jnp oracle);
  * serving reads/writes the KV cache through the paged virtual-memory
    subsystem: prefill writes KV with one translation per page burst
    (paged_copy), decode attends through the page table
    (paged_decode_attention) — the paper's C2 contract end to end;
  * an injectable ``shard(x, name)`` hook lets the launcher pin activation
    shardings without the model importing any mesh machinery.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.vmem import INVALID_PAGE
from repro.kernels import ops, ref  # noqa: F401
from repro.models import layers as L
from repro.models import moe as M
from repro.models.config import ModelConfig

Params = dict[str, Any]
ShardFn = Callable[[jax.Array, str], jax.Array]


def _no_shard(x: jax.Array, name: str) -> jax.Array:
    return x


class PagedKVState(NamedTuple):
    """Serving-side state: paged KV pools + the page table ("satp")."""

    k_pools: jax.Array     # [L, P, page, Hkv, hd]
    v_pools: jax.Array     # [L, P, page, Hkv, hd]
    page_table: jax.Array  # [B, max_pages] int32
    seq_lens: jax.Array    # [B] int32 — tokens currently in cache

    @property
    def page_size(self) -> int:
        return self.k_pools.shape[2]


class TransformerLM:
    """Families: dense | moe | vlm | audio (GQA attention backbones)."""

    def __init__(
        self,
        cfg: ModelConfig,
        *,
        use_kernels: bool = False,
        capacity_factor: float = 1.25,
        remat: bool = True,
        shard: ShardFn | None = None,
        moe_dispatch: str = "sorted",   # "sorted" | "ragged" | "dense"
        remat_policy: str | None = None,  # None | "dots" (§Perf cell B)
        kv_dtype: str = "native",       # "native" | "int8" (§Perf cell A)
        kernel_mesh=None,               # ('kv','hd') mesh for serve kernels
    ):
        assert cfg.family in ("dense", "moe", "vlm", "audio"), cfg.family
        self.cfg = cfg
        self.use_kernels = use_kernels
        #: with a >1-device ('kv','hd') mesh the serve paths dispatch the
        #: Pallas kernels through the shard_map wrappers in kernels.ops —
        #: each device runs the kernel on its local KV-pool slice (see the
        #: ops module docstring); the executor binds this via
        #: ``serve.executor._mesh_kernel_model``
        self.kernel_mesh = kernel_mesh
        self.capacity_factor = capacity_factor
        self.remat = remat
        self.shard = shard or _no_shard
        self.moe_dispatch = moe_dispatch
        self.remat_policy = remat_policy
        self.kv_dtype = kv_dtype
        self.dtype = {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[
            cfg.param_dtype
        ]
        # scan unit: a superblock of `moe_every` layers; for interleaved MoE
        # (llama4: moe_every=2) only the last layer of each group routes.
        self.moe_every = cfg.moe_every if cfg.family == "moe" else 1
        assert cfg.num_layers % self.moe_every == 0, (
            cfg.num_layers, self.moe_every
        )
        self.n_super = cfg.num_layers // self.moe_every

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------

    def _init_block(self, key, is_moe: bool) -> Params:
        cfg, dt = self.cfg, self.dtype
        ks = jax.random.split(key, 4)
        p: Params = {
            "ln1": L.rmsnorm_init(cfg.d_model, dt),
            "attn": L.attention_init(ks[0], cfg, dt),
            "ln2": L.rmsnorm_init(cfg.d_model, dt),
        }
        if is_moe:
            p["mlp"] = M.moe_init(ks[1], cfg, dt)
        else:
            p["mlp"] = L.swiglu_init(ks[1], cfg.d_model, cfg.d_ff, dt)
        return p

    def _is_moe_sub(self, i: int) -> bool:
        return self.cfg.family == "moe" and i == self.moe_every - 1

    def _init_superblock(self, key) -> Params:
        ks = jax.random.split(key, self.moe_every)
        return {
            f"sub{i}": self._init_block(ks[i], self._is_moe_sub(i))
            for i in range(self.moe_every)
        }

    def init(self, key) -> Params:
        cfg, dt = self.cfg, self.dtype
        k_emb, k_blocks, k_head = jax.random.split(key, 3)
        block_keys = jax.random.split(k_blocks, self.n_super)
        stacked = jax.vmap(self._init_superblock)(block_keys)
        p: Params = {
            "embed": L.embed_init(k_emb, cfg.vocab_size, cfg.d_model, dt),
            "blocks": stacked,
            "ln_f": L.rmsnorm_init(cfg.d_model, dt),
        }
        if cfg.family == "audio" and cfg.num_codebooks > 1:
            # per-codebook embeddings + heads (MusicGen over EnCodec streams)
            p["embed"] = jax.vmap(
                lambda k: L.embed_init(k, cfg.vocab_size, cfg.d_model, dt)
            )(jax.random.split(k_emb, cfg.num_codebooks))
            p["head"] = jax.vmap(
                lambda k: L.dense_init(k, cfg.d_model, cfg.vocab_size, dt)
            )(jax.random.split(k_head, cfg.num_codebooks))
        elif not cfg.tie_embeddings:
            p["head"] = L.dense_init(k_head, cfg.d_model, cfg.vocab_size, dt)
        return p

    # ------------------------------------------------------------------
    # embedding / logits
    # ------------------------------------------------------------------

    def embed(self, params: Params, tokens: jax.Array) -> jax.Array:
        cfg = self.cfg
        if cfg.family == "audio" and cfg.num_codebooks > 1:
            # tokens [..., K]: sum of per-codebook embeddings (EnCodec streams)
            per_book = jax.vmap(
                lambda e, t: e[t], in_axes=(0, -1), out_axes=-2
            )(params["embed"], tokens)            # [..., K, D]
            return per_book.sum(axis=-2).astype(self.dtype)
        return params["embed"][tokens]

    def logits_fn(self, params: Params, h: jax.Array) -> jax.Array:
        cfg = self.cfg
        if cfg.family == "audio" and cfg.num_codebooks > 1:
            return jnp.einsum("...d,kdv->...kv", h, params["head"])
        if cfg.tie_embeddings:
            return h @ params["embed"].T
        return h @ params["head"]

    # ------------------------------------------------------------------
    # training forward
    # ------------------------------------------------------------------

    def _block_apply(
        self, p: Params, x: jax.Array, positions: jax.Array, is_moe: bool
    ) -> tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        x = self.shard(x, "act_btd")
        h = L.attention_train(
            p["attn"], L.rmsnorm(p["ln1"], x, cfg.norm_eps), positions, cfg,
            use_kernel=self.use_kernels,
        )
        x = x + h
        hn = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        aux = jnp.float32(0.0)
        if is_moe:
            b, s, d = hn.shape
            if self.moe_dispatch == "sorted":
                # per-row groups: dispatch stays local to the data shard
                ff, aux = M.moe_apply_sorted_rows(
                    p["mlp"], hn,
                    num_experts=cfg.num_experts, k=cfg.experts_per_token,
                    capacity_factor=self.capacity_factor,
                )
                return self.shard(x + ff, "act_btd"), aux
            elif self.moe_dispatch == "ragged":
                ff, aux = M.moe_apply_ragged(
                    p["mlp"], hn.reshape(b * s, d),
                    num_experts=cfg.num_experts, k=cfg.experts_per_token,
                )
            else:
                ff, aux = M.moe_apply_dense(
                    p["mlp"], hn.reshape(b * s, d),
                    num_experts=cfg.num_experts, k=cfg.experts_per_token,
                )
            ff = ff.reshape(b, s, d)
        else:
            ff = L.swiglu(p["mlp"], hn)
        return self.shard(x + ff, "act_btd"), aux

    def _superblock_apply(
        self, sb: Params, x: jax.Array, positions: jax.Array
    ) -> tuple[jax.Array, jax.Array]:
        aux = jnp.float32(0.0)
        for i in range(self.moe_every):
            x, a = self._block_apply(
                sb[f"sub{i}"], x, positions, self._is_moe_sub(i)
            )
            aux = aux + a
        return x, aux

    def forward(
        self,
        params: Params,
        tokens: jax.Array,                    # [B, S] (or [B, S, K] audio)
        positions: jax.Array | None = None,   # [B, S] or [3, B, S] (mrope)
        vision_embeds: jax.Array | None = None,  # [B, Nvis, D] stub frontend
    ) -> tuple[jax.Array, jax.Array]:
        """Returns (hidden [B, S, D], aux_loss scalar)."""
        cfg = self.cfg
        b, s = tokens.shape[:2]
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(s), (b, s))
            if cfg.mrope_sections:
                positions = jnp.broadcast_to(positions, (3, b, s))
        x = self.embed(params, tokens)
        if vision_embeds is not None:
            nvis = vision_embeds.shape[1]
            x = jnp.concatenate(
                [vision_embeds.astype(x.dtype), x[:, nvis:]], axis=1
            )
        def body(carry, sb_params):
            return self._superblock_apply(sb_params, carry, positions)

        if self.remat and self.remat_policy == "dots":
            # save matmul outputs: the backward pass re-gathers FSDP weights
            # once instead of twice (collective term down, memory term up)
            f = jax.checkpoint(
                body,
                policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            )
        elif self.remat:
            f = jax.checkpoint(body)
        else:
            f = body
        x, auxs = jax.lax.scan(f, x, params["blocks"])
        aux = auxs.mean() if cfg.family == "moe" else jnp.float32(0.0)
        return L.rmsnorm(params["ln_f"], x, cfg.norm_eps), aux

    def loss(self, params: Params, batch: dict[str, jax.Array]) -> tuple[
        jax.Array, dict[str, jax.Array]
    ]:
        """batch: tokens, labels, [mask], [positions], [vision_embeds]."""
        h, aux = self.forward(
            params, batch["tokens"], batch.get("positions"),
            batch.get("vision_embeds"),
        )
        logits = self.logits_fn(params, h)
        logits = self.shard(logits, "logits")
        if self.cfg.family == "audio" and self.cfg.num_codebooks > 1:
            # mean over codebook heads
            losses = jax.vmap(
                lambda lg, lb: L.softmax_xent(lg, lb, batch.get("mask")),
                in_axes=(-2, -1),
            )(logits, batch["labels"])
            xent = losses.mean()
        else:
            xent = L.softmax_xent(logits, batch["labels"], batch.get("mask"))
        total = xent + 0.01 * aux
        return total, {"xent": xent, "aux": aux}

    # ------------------------------------------------------------------
    # serving: prefill + paged decode
    # ------------------------------------------------------------------

    KV_INT8_SCALE = 24.0  # fixed-point scale (values are post-norm, O(1))

    def _kv_store_dtype(self):
        return jnp.int8 if self.kv_dtype == "int8" else self.dtype

    def _serve_kernel_mesh(self):
        """The ('kv','hd') mesh the serve-path kernels shard_map over, or
        None for a plain single-device trace.  Only live when kernels are:
        the jnp paths need no shard_map (GSPMD partitions them freely)."""
        m = getattr(self, "kernel_mesh", None)
        if self.use_kernels and m is not None and m.size > 1:
            return m
        return None

    def _kv_quant(self, x: jax.Array) -> jax.Array:
        if self.kv_dtype != "int8":
            return x
        return jnp.clip(
            jnp.round(x.astype(jnp.float32) * self.KV_INT8_SCALE), -127, 127
        ).astype(jnp.int8)

    def init_kv_state(
        self, batch: int, num_pages: int, page_size: int, max_pages: int
    ) -> PagedKVState:
        cfg = self.cfg
        shape = (cfg.num_layers, num_pages, page_size, cfg.num_kv_heads,
                 cfg.head_dim)
        return PagedKVState(
            k_pools=jnp.zeros(shape, self._kv_store_dtype()),
            v_pools=jnp.zeros(shape, self._kv_store_dtype()),
            page_table=jnp.full((batch, max_pages), -1, jnp.int32),
            seq_lens=jnp.zeros((batch,), jnp.int32),
        )

    def _block_serve_qkv(self, p, x, positions):
        """Shared q/k/v + rope for serve paths. x [B, T, D]."""
        cfg = self.cfg
        q, k, v = L.qkv_project(p["attn"], L.rmsnorm(p["ln1"], x, cfg.norm_eps), cfg)
        if cfg.mrope_sections:
            pos3 = jnp.broadcast_to(positions, (3,) + positions.shape)
            q = L.apply_mrope(q, pos3, cfg.rope_theta, cfg.mrope_sections)
            k = L.apply_mrope(k, pos3, cfg.rope_theta, cfg.mrope_sections)
        else:
            q = L.apply_rope(q, positions, cfg.rope_theta)
            k = L.apply_rope(k, positions, cfg.rope_theta)
        return q, k, v

    def _ffn_serve(self, p, x, is_moe: bool):
        cfg = self.cfg
        hn = L.rmsnorm(p["ln2"], x, cfg.norm_eps)
        if is_moe:
            shp = hn.shape
            n_tok = int(np.prod(shp[:-1]))
            if n_tok <= 2048:
                # decode: tiny token count — drop-free ragged dispatch
                # (vmap-safe across serve groups)
                ff, _ = M.moe_apply_ragged_batched(
                    p["mlp"], hn.reshape(-1, shp[-1]),
                    num_experts=cfg.num_experts, k=cfg.experts_per_token,
                )
            else:
                # prefill: per-row sorted dispatch with generous capacity
                # (cf=2.0: drops are astronomically unlikely; keeps the
                # buffers data-shard-local)
                ff, _ = M.moe_apply_sorted_rows(
                    p["mlp"], hn.reshape(-1, shp[-2], shp[-1]),
                    num_experts=cfg.num_experts, k=cfg.experts_per_token,
                    capacity_factor=2.0,
                )
            return x + ff.reshape(shp)
        return x + L.swiglu(p["mlp"], hn)

    def _group_pools(self, pools: jax.Array) -> jax.Array:
        """[L, P, ...] -> [n_super, moe_every, P, ...] for superblock scans."""
        return pools.reshape(
            (self.n_super, self.moe_every) + pools.shape[1:]
        )

    def _ungroup_pools(self, pools: jax.Array) -> jax.Array:
        return pools.reshape((self.cfg.num_layers,) + pools.shape[2:])

    @functools.partial(jax.jit, static_argnums=(0,))
    def prefill(
        self,
        params: Params,
        tokens: jax.Array,        # [B, S] padded prompts
        prompt_lens: jax.Array,   # [B] true lengths
        state: PagedKVState,
        vision_embeds: jax.Array | None = None,
    ) -> tuple[jax.Array, PagedKVState]:
        """Run prompts, write KV through the page table (burst copies).

        Returns (last-token logits [B, V...], updated state with
        seq_lens = prompt_lens).
        """
        cfg = self.cfg
        b, s = tokens.shape[:2]
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        x = self.embed(params, tokens)
        if vision_embeds is not None:
            nvis = vision_embeds.shape[1]
            x = jnp.concatenate(
                [vision_embeds.astype(x.dtype), x[:, nvis:]], axis=1
            )
        page = state.page_size
        hkv, hd = cfg.num_kv_heads, cfg.head_dim
        mesh = self._serve_kernel_mesh()

        def layer(block_p, x, k_pool, v_pool, is_moe):
            q, k, v = self._block_serve_qkv(block_p, x, positions)
            # unit-stride burst write through the page table (C2-burst).
            # The pool copy stores the POOL's dtype: quantized under int8
            # (the copies are dtype-agnostic, so the burst itself narrows),
            # while the chunk's own flash attention below keeps the raw
            # activations — quantization error only enters once pages are
            # re-read through the paged-attention kernels.
            kq, vq = self._kv_quant(k), self._kv_quant(v)
            if mesh is not None:
                # shard_map dispatch: 4-D natural layout to the boundary,
                # merged-W reshape happens shard-locally (kernels/ops.py)
                k_pool = ops.paged_copy_sharded(
                    kq, k_pool, state.page_table, prompt_lens,
                    page_size=page, mesh=mesh,
                )
                v_pool = ops.paged_copy_sharded(
                    vq, v_pool, state.page_table, prompt_lens,
                    page_size=page, mesh=mesh,
                )
            else:
                k_pool = ops.paged_copy(
                    kq.reshape(b, s, hkv * hd),
                    k_pool.reshape(-1, page, hkv * hd),
                    state.page_table, prompt_lens, page_size=page,
                    use_kernel=self.use_kernels,
                ).reshape(k_pool.shape)
                v_pool = ops.paged_copy(
                    vq.reshape(b, s, hkv * hd),
                    v_pool.reshape(-1, page, hkv * hd),
                    state.page_table, prompt_lens, page_size=page,
                    use_kernel=self.use_kernels,
                ).reshape(v_pool.shape)
            qt, kt, vt = (t.swapaxes(1, 2) for t in (q, k, v))
            if mesh is not None:
                o = ops.flash_attention_sharded(qt, kt, vt, causal=True,
                                                mesh=mesh)
            elif self.use_kernels:
                o = ops.flash_attention(qt, kt, vt, causal=True)
            elif s > 1024:
                o = ref.chunked_attention_ref(qt, kt, vt, causal=True)
            else:
                o = ref.flash_attention_ref(qt, kt, vt, causal=True)
            x = x + o.swapaxes(1, 2).reshape(b, s, -1) @ block_p["attn"]["wo"]
            x = self._ffn_serve(block_p, x, is_moe)
            return x, k_pool, v_pool

        def body(carry, xs):
            x = carry
            sb, k_pools_g, v_pools_g = xs   # pools [moe_every, P, ...]
            kps, vps = [], []
            for i in range(self.moe_every):
                x, kp, vp = layer(
                    sb[f"sub{i}"], x, k_pools_g[i], v_pools_g[i],
                    self._is_moe_sub(i),
                )
                kps.append(kp)
                vps.append(vp)
            return x, (jnp.stack(kps), jnp.stack(vps))

        x, (k_pools, v_pools) = jax.lax.scan(
            body, x,
            (params["blocks"], self._group_pools(state.k_pools),
             self._group_pools(state.v_pools)),
        )
        k_pools = self._ungroup_pools(k_pools)
        v_pools = self._ungroup_pools(v_pools)
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        last = jnp.take_along_axis(
            x, jnp.maximum(prompt_lens - 1, 0)[:, None, None], axis=1
        )[:, 0]
        logits = self.logits_fn(params, last)
        new_state = PagedKVState(
            k_pools, v_pools, state.page_table, prompt_lens.astype(jnp.int32)
        )
        return logits, new_state

    @functools.partial(jax.jit, static_argnums=(0,))
    def prefill_continue(
        self,
        params: Params,
        tokens: jax.Array,        # [B, S] padded continuation chunks
        start_lens: jax.Array,    # [B] tokens already cached per sequence
        chunk_lens: jax.Array,    # [B] true lengths of the new chunks
        state: PagedKVState,
    ) -> tuple[jax.Array, PagedKVState]:
        """Chunked prefill at arbitrary start offsets (continuation).

        Extends sequences that already have ``start_lens`` tokens in the
        paged cache by a chunk of new tokens: KV is written through the page
        table with one translation per burst starting at the (not
        necessarily page-aligned) logical offset (``paged_copy_at``), and
        each chunk query attends causally over cache + chunk through the
        page table (``paged_prefill_attention`` — the Pallas kernel streams
        KV pages per query block; the jnp oracle gathers the full logical
        prefix).  This replaces one-token-at-a-time teacher forcing for
        forked/continued requests with a single device step per chunk, and
        the batch axis lets same-step forked admissions run as one call.

        The host must have mapped pages covering positions
        ``[start, start + chunk)`` (VirtualMemory.append_tokens).
        Returns (last-chunk-token logits [B, V...], state with
        seq_lens = start_lens + chunk_lens).
        """
        cfg = self.cfg
        b, s = tokens.shape[:2]
        page = state.page_size
        hkv, hd, g = cfg.num_kv_heads, cfg.head_dim, cfg.q_per_kv
        positions = start_lens[:, None] + jnp.arange(s)[None, :]    # [B, S]
        x = self.embed(params, tokens)
        kv_scale = (1.0 / self.KV_INT8_SCALE
                    if self.kv_dtype == "int8" else None)
        mesh = self._serve_kernel_mesh()

        def layer(block_p, x, k_pool, v_pool, is_moe):
            q, k, v = self._block_serve_qkv(block_p, x, positions)
            if mesh is not None:
                k_pool = ops.paged_copy_at_sharded(
                    self._kv_quant(k), k_pool, state.page_table,
                    start_lens, chunk_lens, page_size=page, mesh=mesh,
                )
                v_pool = ops.paged_copy_at_sharded(
                    self._kv_quant(v), v_pool, state.page_table,
                    start_lens, chunk_lens, page_size=page, mesh=mesh,
                )
            else:
                k_pool = ops.paged_copy_at(
                    self._kv_quant(k).reshape(b, s, hkv * hd),
                    k_pool.reshape(-1, page, hkv * hd),
                    state.page_table, start_lens, chunk_lens, page_size=page,
                    use_kernel=self.use_kernels,
                ).reshape(k_pool.shape)
                v_pool = ops.paged_copy_at(
                    self._kv_quant(v).reshape(b, s, hkv * hd),
                    v_pool.reshape(-1, page, hkv * hd),
                    state.page_table, start_lens, chunk_lens, page_size=page,
                    use_kernel=self.use_kernels,
                ).reshape(v_pool.shape)
            # attend through the page table: causal mask on absolute
            # positions (cache + committed chunk prefix).  int8 pools ride
            # the same kernel dispatch — kv_scale is a scalar-prefetch
            # operand and the tiles dequantize in VMEM (kernels/ops.py).
            if mesh is not None:
                o = ops.paged_prefill_attention_sharded(
                    q.reshape(b, s, hkv, g, hd), k_pool, v_pool,
                    state.page_table, start_lens, page_size=page, mesh=mesh,
                    kv_scale=kv_scale,
                )
            else:
                o = ops.paged_prefill_attention(
                    q.reshape(b, s, hkv, g, hd), k_pool, v_pool,
                    state.page_table, start_lens, page_size=page,
                    use_kernel=self.use_kernels, kv_scale=kv_scale,
                )
            o = o.reshape(b, s, hkv * g * hd)
            x = x + o @ block_p["attn"]["wo"]
            x = self._ffn_serve(block_p, x, is_moe)
            return x, k_pool, v_pool

        def body(carry, xs):
            x = carry
            sb, k_pools_g, v_pools_g = xs
            kps, vps = [], []
            for i in range(self.moe_every):
                x, kp, vp = layer(
                    sb[f"sub{i}"], x, k_pools_g[i], v_pools_g[i],
                    self._is_moe_sub(i),
                )
                kps.append(kp)
                vps.append(vp)
            return x, (jnp.stack(kps), jnp.stack(vps))

        x, (k_pools, v_pools) = jax.lax.scan(
            body, x,
            (params["blocks"], self._group_pools(state.k_pools),
             self._group_pools(state.v_pools)),
        )
        k_pools = self._ungroup_pools(k_pools)
        v_pools = self._ungroup_pools(v_pools)
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        last = jnp.take_along_axis(
            x, jnp.maximum(chunk_lens - 1, 0)[:, None, None], axis=1
        )[:, 0]
        logits = self.logits_fn(params, last)
        new_lens = (start_lens + chunk_lens).astype(jnp.int32)
        return logits, PagedKVState(
            k_pools, v_pools, state.page_table, new_lens
        )

    @functools.partial(jax.jit, static_argnums=(0,))
    def decode_step(
        self,
        params: Params,
        tokens: jax.Array,       # [B] (or [B, K] audio) freshly sampled
        state: PagedKVState,
    ) -> tuple[jax.Array, PagedKVState]:
        """One token: write KV at position seq_lens, attend through pages.

        The host must already have mapped a page covering position
        ``seq_lens`` (VirtualMemory.append_tokens — the page-fault path).
        """
        cfg = self.cfg
        b = tokens.shape[0]
        page = state.page_size
        hkv, hd, g = cfg.num_kv_heads, cfg.head_dim, cfg.q_per_kv
        pos = state.seq_lens                      # [B] position of new token
        x = self.embed(params, tokens)[:, None, :]  # [B, 1, D]
        # (frame, offset) of the new token in every layer's pool (one
        # translation per element here — B independent sequences, B
        # translations).  Inactive batch slots (unmapped page-table rows)
        # are routed to the pool's LAST row, which the serving engine
        # reserves as scratch — never to a live frame.
        frames = jnp.take_along_axis(
            state.page_table, (pos // page)[:, None], axis=1
        )[:, 0]
        inactive = frames < 0
        frames = jnp.where(inactive, state.k_pools.shape[1] - 1, frames)
        offs = jnp.where(inactive, page - 1, pos % page)            # [B]
        new_lens = jnp.where(inactive, pos, pos + 1)
        kv_scale = (1.0 / self.KV_INT8_SCALE
                    if self.kv_dtype == "int8" else None)
        mesh = self._serve_kernel_mesh()

        # The stacked [L, P, page, Hkv, hd] pools ride the scan's carry
        # whole, from the donated input to the output: each layer writes
        # its new rows into the stack in place and the kernel reads layer
        # ``li`` of the stack by index, so no layer's pool is sliced out,
        # re-laid-out or re-stacked.
        def layer(block_p, x, k_pools, v_pools, li, is_moe):
            q, k, v = self._block_serve_qkv(block_p, x, pos[:, None])
            # the single-token row scatter is plain jnp — GSPMD shards it
            k_pools = k_pools.at[li, frames, offs].set(self._kv_quant(k[:, 0]))
            v_pools = v_pools.at[li, frames, offs].set(self._kv_quant(v[:, 0]))
            qh = q[:, 0].reshape(b, hkv, g, hd)
            if mesh is not None:
                o = ops.paged_decode_attention_sharded(
                    qh, k_pools, v_pools, state.page_table, new_lens, li,
                    page_size=page, mesh=mesh, kv_scale=kv_scale,
                )
            else:
                o = ops.paged_decode_attention(
                    qh, k_pools, v_pools, state.page_table, new_lens, li,
                    page_size=page, use_kernel=self.use_kernels,
                    kv_scale=kv_scale,
                )                                 # [B, Hkv, G, hd]
            x = x + (o.reshape(b, 1, hkv * g * hd) @ block_p["attn"]["wo"])
            x = self._ffn_serve(block_p, x, is_moe)
            return x, k_pools, v_pools

        def body(carry, xs):
            x, k_pools, v_pools = carry
            sb, si = xs
            for i in range(self.moe_every):
                x, k_pools, v_pools = layer(
                    sb[f"sub{i}"], x, k_pools, v_pools,
                    si * self.moe_every + i, self._is_moe_sub(i),
                )
            return (x, k_pools, v_pools), None

        (x, k_pools, v_pools), _ = jax.lax.scan(
            body, (x, state.k_pools, state.v_pools),
            (params["blocks"], jnp.arange(self.n_super, dtype=jnp.int32)),
        )
        x = L.rmsnorm(params["ln_f"], x, cfg.norm_eps)
        logits = self.logits_fn(params, x[:, 0])
        return logits, PagedKVState(
            k_pools, v_pools, state.page_table, new_lens
        )

    def decode_multi_step(
        self,
        params: Params,
        tokens: jax.Array,       # [B] (or [B, K] audio) last sampled tokens
        state: PagedKVState,
        steps_left: jax.Array,   # [B] int32 — active inner steps per lane
        rng: jax.Array,          # PRNG key (threaded; ignored when greedy)
        temperature: jax.Array,  # scalar     (ignored when greedy)
        *,
        horizon: int,
        greedy: bool,
    ) -> tuple[jax.Array, PagedKVState, jax.Array]:
        """Fused K-token decode: ``lax.scan`` over ``horizon`` chained
        :meth:`decode_step` calls with ON-DEVICE sampling.

        The scalar/OS plane intervenes once per *horizon*, not once per
        token (the AraOS amortization contract applied to the decode loop):
        each inner step writes KV at ``seq_lens``, attends through the page
        table, samples the next token on device (greedy argmax, or
        temperature/categorical with the PRNG key split exactly like the
        host path — one split per step, carry ``split(key)[0]``, consume
        ``split(key)[1]`` — so fused and step-wise stochastic streams are
        identical), and feeds it straight back into the next step.

        Per-lane retirement is masked on device: lane ``i`` is active at
        inner step ``t`` iff ``t < steps_left[i]``.  Inactive lanes get
        their page-table row masked to the invalid sentinel, which routes
        their KV write to the reserved scratch frame and freezes their
        ``seq_lens`` (``decode_step``'s existing guard) — the table itself
        is never rewritten.  The host must have pre-faulted pages covering
        every position the horizon touches (``VirtualMemory.
        append_tokens_batch``).

        Returns ``(token_block [horizon, B, ...], state, rng)``; block rows
        at ``t >= steps_left[i]`` are scratch output the caller discards.
        """
        ptab = state.page_table

        def body(carry, t):
            toks, k_pools, v_pools, seq_lens, key = carry
            active = t < steps_left                           # [B] bool
            masked = jnp.where(active[:, None], ptab, INVALID_PAGE)
            st = PagedKVState(k_pools, v_pools, masked, seq_lens)
            logits, ns = self.decode_step(params, toks, st)
            if greedy:
                new_tok = jnp.argmax(logits, axis=-1)
            else:
                key, sub = jax.random.split(key)
                new_tok = jax.random.categorical(
                    sub, logits / temperature, axis=-1
                )
            new_tok = new_tok.astype(toks.dtype)
            lane = active.reshape((-1,) + (1,) * (toks.ndim - 1))
            toks = jnp.where(lane, new_tok, toks)
            return (toks, ns.k_pools, ns.v_pools, ns.seq_lens, key), new_tok

        (tokens, k_pools, v_pools, seq_lens, rng), block = jax.lax.scan(
            body,
            (tokens, state.k_pools, state.v_pools, state.seq_lens, rng),
            jnp.arange(horizon),
        )
        return block, PagedKVState(k_pools, v_pools, ptab, seq_lens), rng
