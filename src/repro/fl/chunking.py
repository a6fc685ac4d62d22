"""Chunked client mapping — vmap semantics at O(chunk) memory.

``chunked_vmap`` is the one primitive the round engine and the
SecureServer share for bounding the client axis: with ``chunk=None`` (or
``chunk >= C``) it is *exactly* ``jax.vmap`` — the same traced graph,
bit-for-bit with the unchunked path — and otherwise the leading client
axis is padded to a multiple of ``chunk``, reshaped to ``(k, chunk,
...)`` blocks and swept sequentially with ``jax.lax.map`` (vmap inside
each block), so peak working memory is O(chunk x per-client footprint)
instead of O(C x per-client footprint).

The padding/blocking scheme is factored out (``pad_to_blocks`` /
``unblock`` / ``block_valid``) because the streaming-aggregation
subsystem (fl/streaming.py) sweeps the *same* blocks with a
``jax.lax.scan`` that folds each block into a constant-size AggState
instead of stacking outputs — one partition definition keeps the two
sweeps row-aligned, which the bitwise streaming == dense contract
depends on.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..sharding import ShardMismatchError


def pad_to_blocks(args, chunk: int) -> Tuple[tuple, int, int]:
    """Pad the shared leading axis C of every array in the ``args`` pytree
    to a multiple of ``chunk`` (with copies of the first rows) and reshape
    each leaf to ``(k, chunk, ...)`` blocks.  Returns ``(blocks, k, C)``.
    Padding rows carry no meaning — consumers must discard their outputs
    (``unblock``) or zero their contributions (``block_valid``)."""
    leaves = jax.tree.leaves(args)
    if not leaves:
        raise ValueError("pad_to_blocks needs at least one array argument")
    C = leaves[0].shape[0]
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if chunk > C:
        # x[:pad] cannot supply more than C padding rows; callers clamp
        # (chunked_vmap is plain vmap and stream_aggregate folds a single
        # C-sized block when chunk >= C) — fail loudly for new consumers
        raise ValueError(
            f"chunk ({chunk}) exceeds the leading axis ({C}); take the "
            f"vmap / single-block path for chunk >= C")
    k = -(-C // chunk)                       # ceil(C / chunk) blocks
    pad = k * chunk - C

    def to_blocks(x):
        if pad:
            x = jnp.concatenate([x, x[:pad]], axis=0)
        return x.reshape((k, chunk) + x.shape[1:])

    return jax.tree.map(to_blocks, args), k, C


def unblock(out, k: int, chunk: int, C: int):
    """Inverse of ``pad_to_blocks`` on outputs: (k, chunk, ...) blocks ->
    (C, ...) with the padding rows dropped."""
    return jax.tree.map(
        lambda x: x.reshape((k * chunk,) + x.shape[2:])[:C], out)


def block_valid(k: int, chunk: int, C: int) -> jnp.ndarray:
    """(k, chunk) bool mask: True where a block row is a real client,
    False on the padding rows of the final block."""
    return (jnp.arange(k * chunk) < C).reshape(k, chunk)


def resolve_shards(shards: int, k: int) -> int:
    """Clamp a requested shard count to the largest divisor of ``k``
    (the block count) not exceeding it — contiguous groups must tile the
    block axis exactly, and a non-divisible request degrades gracefully
    instead of failing inside a trace."""
    s = max(1, min(int(shards), k))
    while k % s:
        s -= 1
    return s


def group_blocks(blocks, k: int, shards: int):
    """Reshape ``(k, chunk, ...)`` blocks into ``(shards, k // shards,
    chunk, ...)`` contiguous shard groups — shard ``j`` owns blocks
    ``[j*k/S, (j+1)*k/S)``, i.e. a contiguous client range, which is
    what keeps each shard's left fold row-aligned with the sequential
    sweep (fl/streaming.py's canonical merge-order contract)."""
    if k % shards:
        raise ShardMismatchError(
            f"shards ({shards}) must divide the block count ({k}); "
            f"use resolve_shards")
    return jax.tree.map(
        lambda x: x.reshape((shards, k // shards) + x.shape[1:]), blocks)


def resolve_pods(pods: Optional[int], k: int, auto: int = 1) -> int:
    """The pod count the two-tier fold actually uses.

    ``pods=None`` derives from ``auto`` (the mesh's pod-axis size),
    clamped to the largest divisor of the block count ``k`` — a mesh
    shape can never break an off-mesh-equivalent run.  An **explicit**
    ``pods`` is a contract, not a hint: a value that does not divide
    ``k`` raises the named :class:`~repro.sharding.ShardMismatchError`
    (before this error class, the mismatch surfaced as a reshape
    failure deep inside the traced fold)."""
    if pods is None:
        return resolve_shards(auto, k)
    p = int(pods)
    if p < 1:
        raise ShardMismatchError(f"pods must be >= 1, got {p}")
    if p > k or k % p:
        raise ShardMismatchError(
            f"pods ({p}) must divide the padded block count ({k}); pick a "
            f"client_chunk so ceil(C / chunk) tiles the pods, or pass "
            f"pods=None to clamp to the mesh-derived divisor")
    return p


def group_blocks_2d(blocks, k: int, pods: int, shards: int):
    """Two-level grouping for the hierarchical fold (fl/streaming.py,
    DESIGN.md §9): ``(k, chunk, ...)`` blocks -> ``(pods, shards,
    k / (pods·shards), chunk, ...)``.

    Pod ``p`` owns the contiguous block range ``[p·k/P, (p+1)·k/P)``
    (pod-major — the same contiguous client ranges the ``("pod",
    "data")`` client sharding places on pod ``p``'s devices), and
    within a pod shard ``s`` owns a contiguous sub-range — so every
    ``(p, s)`` lane's left fold is row-aligned with the sequential
    sweep, and flattening the first two axes recovers ``group_blocks``
    with ``pods·shards`` flat groups."""
    if k % pods:
        raise ShardMismatchError(
            f"pods ({pods}) must divide the block count ({k}); "
            f"use resolve_pods")
    if (k // pods) % shards:
        raise ShardMismatchError(
            f"per-pod shards ({shards}) must divide the per-pod block "
            f"count ({k // pods}); use resolve_shards")
    return jax.tree.map(
        lambda x: x.reshape(
            (pods, shards, k // (pods * shards)) + x.shape[1:]), blocks)


def chunked_vmap(fn, args: tuple, chunk: Optional[int] = None):
    """Map ``fn`` over the shared leading axis of every array in ``args``.

    ``args`` is a tuple of pytrees whose leaves all carry the same leading
    dimension C (the client axis).  Returns exactly what
    ``jax.vmap(fn)(*args)`` returns; ``chunk`` only bounds how much of the
    axis is in flight at once.  Padding rows (copies of the first rows)
    are computed and discarded — they never reach the output.
    """
    leaves = jax.tree.leaves(args)
    if not leaves:
        raise ValueError("chunked_vmap needs at least one array argument")
    C = leaves[0].shape[0]
    # the map's padding, output stacking and unblocking are the flatten
    # stage; the stage scope of ``fn`` nests inside and wins (DESIGN.md
    # §11)
    with jax.named_scope("flatten"):
        if chunk is None or chunk >= C:
            return jax.vmap(fn)(*args)
        blocks, k, C = pad_to_blocks(args, chunk)
        out = jax.lax.map(lambda a: jax.vmap(fn)(*a), blocks)
        return unblock(out, k, chunk, C)
