"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run must set XLA_FLAGS before any
device query.
"""

from __future__ import annotations

import jax


def _mk(shape, axes, devices=None) -> jax.sharding.Mesh:
    # Auto axes: GSPMD propagates shardings; jax.make_mesh defaults to
    # Explicit, which would make every sharding part of the array's type
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
        devices=devices,
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """16x16 single pod (256 chips) or 2x16x16 (512 chips, 2 pods)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mk(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over the locally available devices (tests, examples)."""
    return _mk((data, model), ("data", "model"))


def make_serve_mesh(
    num_kv_heads: int, head_dim: int, *, multi_pod: bool = False,
    model_size: int = 16,
) -> jax.sharding.Mesh:
    """Serving mesh: the production topology with the model axis viewed as
    a 2-D ('kv', 'hd') tile.

    Same devices, same order, same physical 16x16(x2) topology as
    ``make_production_mesh`` — only the *logical* factorization of the
    model axis changes, so KV pools can shard jointly over KV heads and
    head_dim without GSPMD's "involuntary full rematerialization" (it
    cannot reshard a 1-D hd-sharding into the (kv x hd) tiling attention
    needs; see EXPERIMENTS.md §Perf iteration 1).
    """
    kv = 1
    for cand in (16, 8, 4, 2, 1):
        if cand <= model_size and num_kv_heads % cand == 0:
            kv = cand
            break
    hd = model_size // kv
    if head_dim % hd != 0:  # degrade: replicate the remainder onto kv
        kv, hd = 1, model_size
        if head_dim % hd != 0:
            raise ValueError(
                f"cannot factor model axis for Hkv={num_kv_heads}, "
                f"head_dim={head_dim}"
            )
    shape = (2, 16, kv, hd) if multi_pod else (16, kv, hd)
    axes = (("pod",) if multi_pod else ()) + ("data", "kv", "hd")
    return _mk(shape, axes)


def make_host_serve_mesh(
    num_kv_heads: int, head_dim: int, num_devices: int | None = None,
) -> jax.sharding.Mesh:
    """('kv', 'hd') serving mesh over the *locally visible* devices.

    The executor-facing dual of :func:`make_serve_mesh`: same logical
    factorization of the model axis into a 2-D (kv x hd) tile, but sized
    to whatever this process can see — 8 forced host devices under
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` in CI, a TPU
    slice in production, 1 CPU device in plain local runs (a 1x1 mesh:
    the sharded code path with replicated layouts).

    Picks the factorization using the MOST devices such that ``kv``
    divides ``num_kv_heads`` and ``hd`` divides ``head_dim`` (ties prefer
    the kv axis — head-parallel attention needs no cross-device
    reductions, so it tracks the single-device float stream closest);
    devices beyond ``kv * hd`` are simply left out of the mesh.
    ``num_devices`` caps the search (clamped to what is visible).
    """
    visible = len(jax.devices())
    n = min(num_devices, visible) if num_devices is not None else visible
    if n < 1:
        raise ValueError("need at least one device")
    best: tuple[int, int] | None = None
    for size in range(n, 0, -1):
        for kv in range(min(size, num_kv_heads), 0, -1):
            if size % kv or num_kv_heads % kv:
                continue
            hd = size // kv
            if head_dim % hd == 0:
                best = (kv, hd)
                break
        if best is not None:
            break
    kv, hd = best  # (1, 1) always factors, so best is never None
    return _mk((kv, hd), ("kv", "hd"))


def make_device_mesh(device) -> jax.sharding.Mesh:
    """A 1x1 ('kv', 'hd') serve mesh on one given device.

    Places a single-device executor on a chip of its own: the router's
    one-chip replicas on a multi-chip host each take one of these.
    """
    return _mk((1, 1), ("kv", "hd"), devices=[device])


def kv_partition_axes(
    mesh: jax.sharding.Mesh, num_kv_heads: int, head_dim: int,
) -> tuple[str | None, str | None]:
    """Per-dim mesh axes ``(kv_axis, hd_axis)`` for KV-shaped operands.

    THE single source of truth for how (Hkv, head_dim) dims map onto a
    ('kv', 'hd') serve mesh: an axis is used only when it exists on the
    mesh AND its extent divides the dim; otherwise that dim degrades to
    replicated (``None``).  ``launch.specs.executor_state_shardings``
    (the executor's persistent-state layout) and the shard_map kernel
    dispatch wrappers in ``kernels.ops`` both derive their specs from
    this, so the per-device pool slice a Pallas kernel sees is by
    construction the same slice the executor committed.
    """
    def ok(dim: int, ax: str) -> str | None:
        if ax not in mesh.axis_names or dim % mesh.shape[ax]:
            return None
        return ax

    return ok(num_kv_heads, "kv"), ok(head_dim, "hd")


def dp_axes(mesh: jax.sharding.Mesh) -> tuple[str, ...]:
    """The data-parallel axes of a mesh: ('pod', 'data') or ('data',)."""
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def fsdp_axis(mesh: jax.sharding.Mesh) -> str | None:
    """Axis used for parameter sharding (FSDP): intra-pod 'data' only —
    cross-pod parameter all-gathers would traverse DCI every layer."""
    return "data" if "data" in mesh.axis_names else None
