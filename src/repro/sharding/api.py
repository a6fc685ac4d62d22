"""Sharding utilities.

The production mesh has axes ``("pod", "data", "model")`` (multi-pod) or
``("data", "model")`` (single pod).  FL clients live on the (pod, data)
axes; tensor/expert parallelism lives on ``model``.

Model code only ever constrains the ``model`` axis (via :func:`shard`),
because the FL round step runs inside ``jax.shard_map`` that is *manual*
over the client axes and *auto* over ``model`` — constraints that name a
manual axis would be rejected there.  Batch/client sharding is applied by
the launcher on the function boundary instead.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MODEL_AXIS = "model"
POD_AXIS = "pod"
DATA_AXES = ("pod", "data")  # whichever exist in the active mesh

_state = threading.local()


class ShardMismatchError(ValueError):
    """A requested shard/pod count cannot tile the axis it partitions.

    Raised with the offending numbers *named* (count, block count, the
    chunking that produced it) instead of surfacing as a reshape failure
    deep inside a traced fold — the error a user can actually act on
    (pick a ``client_chunk`` so the padded block count tiles, or drop
    the forced count and let the mesh-derived auto value clamp)."""


def get_mesh() -> Optional[Mesh]:
    return getattr(_state, "mesh", None)


def set_mesh(mesh: Optional[Mesh]) -> None:
    _state.mesh = mesh


@contextlib.contextmanager
def use_mesh(mesh: Optional[Mesh]):
    prev = get_mesh()
    set_mesh(mesh)
    try:
        yield mesh
    finally:
        set_mesh(prev)


def shard(x, spec: P):
    """Constrain ``x`` to ``spec`` when a mesh is active; no-op otherwise.

    ``spec`` must only reference the ``model`` axis (see module docstring).
    Inside ``shard_map`` the context mesh carries Manual axis types for the
    client axes, so the constraint must be built against the *abstract*
    mesh from the trace context, not the concrete Auto-typed mesh.
    """
    mesh = get_mesh()
    if mesh is None or MODEL_AXIS not in mesh.axis_names:
        return x
    # skip constraints that cannot tile: forcing e.g. 8 heads onto a 16-way
    # model axis makes the SPMD partitioner fall back to full
    # rematerialization (replicate + repartition) — worse than no hint.
    for dim, name in zip(x.shape, spec):
        if name is None:
            continue
        names = name if isinstance(name, tuple) else (name,)
        size = 1
        for n in names:
            size *= mesh.shape[n]
        if dim % size != 0:
            return x
    am = jax.sharding.get_abstract_mesh()
    if not am.empty and MODEL_AXIS in am.axis_names:
        return jax.lax.with_sharding_constraint(x, NamedSharding(am, spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ----------------------------------------------------------------------
# Client-axis sharding (the federated round engine's contract).
#
# The simulator-side round engine (fl/engine.py) carries the federation
# as stacked arrays with a leading client axis — minibatch stacks,
# (N, D) update/guide matrices.  When a mesh is active that axis is
# sharded over the data axes, mirroring how launch/train.py places one
# client per (pod, data) coordinate; without a mesh (or when the axis
# does not tile) every helper is a no-op so the single-device path is
# untouched.
# ----------------------------------------------------------------------

def _client_axes_in(mesh) -> tuple:
    return tuple(a for a in DATA_AXES if a in mesh.axis_names)


def client_spec(ndim: int, axis: int = 0, mesh: Optional[Mesh] = None):
    """PartitionSpec placing dim ``axis`` (the client axis) on the mesh's
    data axes; None when no mesh / no data axes are available.

    On a multi-pod mesh the spec names the ``("pod", "data")`` *pair*,
    which XLA tiles pod-major: client ``c`` of ``C`` lands on pod
    ``c // (C / pods)`` — contiguous client ranges per pod.  That is the
    **pod-major client layout contract** (DESIGN.md §9): the two-tier
    streaming fold's pod groups (fl/streaming.py) partition the block
    axis into the same contiguous ranges, so "the clients a pod folds"
    and "the clients a pod's devices hold" are the same set."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None:
        return None
    caxes = _client_axes_in(mesh)
    if not caxes:
        return None
    spec = [None] * ndim
    spec[axis] = caxes if len(caxes) > 1 else caxes[0]
    return P(*spec)


def client_sharding(ndim: int, axis: int = 0,
                    mesh: Optional[Mesh] = None) -> Optional[NamedSharding]:
    """NamedSharding for a client-stacked array (None when inapplicable)."""
    mesh = mesh if mesh is not None else get_mesh()
    spec = client_spec(ndim, axis, mesh)
    return None if spec is None else NamedSharding(mesh, spec)


def _client_axis_size(mesh) -> int:
    size = 1
    for a in _client_axes_in(mesh):
        size *= mesh.shape[a]
    return size


def data_shard_count(mesh: Optional[Mesh] = None) -> int:
    """How many ways the active mesh splits the client axis — the
    **product over every DATA_AXES member present** in the mesh (a
    multi-pod mesh counts ``pod x data``, a single-pod mesh just
    ``data``), which is the natural total lane count for the streaming
    fold's tree-reduce (fl/streaming.py).  1 without a mesh or without
    data axes, so the no-mesh path degrades to the sequential sweep."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None:
        return 1
    return _client_axis_size(mesh)


def pod_count(mesh: Optional[Mesh] = None) -> int:
    """Size of the mesh's ``pod`` axis — the auto tier count for the
    hierarchical streaming fold (fl/streaming.py, DESIGN.md §9).  1
    without a mesh or on a single-pod mesh, so the two-tier path
    degrades to the flat single-tier fold."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None or POD_AXIS not in mesh.axis_names:
        return 1
    return mesh.shape[POD_AXIS]


def pod_data_counts(mesh: Optional[Mesh] = None):
    """``(pods, per_pod_shards)`` of the active mesh: the pod-axis size
    and the product of the remaining data axes.  ``pods *
    per_pod_shards == data_shard_count`` always — the two-tier fold
    reorganizes the same lanes into a two-level merge, it never changes
    how many there are."""
    mesh = mesh if mesh is not None else get_mesh()
    p = pod_count(mesh)
    return p, data_shard_count(mesh) // p


def lane_spec(ndim: int, mesh: Optional[Mesh] = None):
    """PartitionSpec for the two-tier fold's lane tensor: dim 0 (the pod
    group axis) on ``pod``, dim 1 (the within-pod shard axis) on
    ``data`` — pod-local folds stay inside their pod's devices and only
    the O(pods·D) partial AggStates cross the interconnect.  None when
    the mesh has no data axes; on a pod-less mesh dim 1 alone is
    placed."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None or ndim < 2:
        return None
    has_pod = POD_AXIS in mesh.axis_names
    caxes = _client_axes_in(mesh)
    if not caxes:
        return None
    spec = [None] * ndim
    if has_pod:
        spec[0] = POD_AXIS
        rest = tuple(a for a in caxes if a != POD_AXIS)
        if rest:
            spec[1] = rest if len(rest) > 1 else rest[0]
    else:
        spec[1] = caxes if len(caxes) > 1 else caxes[0]
    return P(*spec)


def shard_lanes(x):
    """Constrain a ``(pods, shards, ...)`` fold-lane tensor over the
    ``("pod", "data")`` axes (traced code) — :func:`shard_clients`'s
    two-axis twin, with the same degrade-gracefully contract: no-op
    without a mesh, without data axes, or when a lane dim does not tile
    its mesh axis."""
    mesh = get_mesh()
    if mesh is None:
        return x
    spec = lane_spec(x.ndim, mesh)
    if spec is None:
        return x
    for dim, name in zip(x.shape, spec):
        if name is None:
            continue
        names = name if isinstance(name, tuple) else (name,)
        size = 1
        for n in names:
            size *= mesh.shape[n]
        if dim % size != 0:
            return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_clients(x, axis: int = 0):
    """Constrain dim ``axis`` of ``x`` over the data axes (traced code).

    No-op without a mesh, without data axes, or when the dim does not
    tile — the same degrade-gracefully contract as :func:`shard`.
    """
    mesh = get_mesh()
    if mesh is None:
        return x
    caxes = _client_axes_in(mesh)
    if not caxes or x.shape[axis] % _client_axis_size(mesh) != 0:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, client_spec(x.ndim, axis, mesh)))


def sweep_put(tree):
    """Place a sweep group's stacked operands (leading *scenario* axis on
    every leaf) over the mesh's data axes — one batch of runs per data
    coordinate, the sweep engine's placement contract (fl/sweep.py,
    DESIGN.md §8).

    The scenario axis reuses the client sharding with ``axis=0``:
    independent runs are embarrassingly parallel, so they occupy the
    same mesh axes a single run's client axis would.  Degrades per-leaf
    to a no-op without a mesh, without data axes, or when the group
    size does not tile the data-axis size — a partial group still runs,
    just without cross-device parallelism for the remainder.  Inside
    the batched program the per-run client-axis constraints
    (:func:`shard_clients`) no-op whenever the *per-cell* client axis
    does not tile the mesh, so placing the scenario axis here is what
    decides the layout; pick group sizes divisible by
    :func:`data_shard_count` to keep cells device-aligned."""
    mesh = get_mesh()
    if mesh is None:
        return tree

    def put(x):
        if x.shape[0] % _client_axis_size(mesh) != 0:
            return x
        s = client_sharding(x.ndim, 0, mesh)
        return x if s is None else jax.device_put(x, s)
    return jax.tree.map(put, tree)


# ----------------------------------------------------------------------
# Client x model 2D sharding (the tensor-sharded round contract).
#
# When the mesh also carries a non-trivial ``model`` axis, the engine's
# flattened per-client quantities — the (N, D)/(chunk, D) update and
# guide matrices, the (D,) AggState numerator and round delta — shard
# their *last* dim (the flat model dim D) over ``model`` while the
# client dim keeps the (pod, data) placement above.  Every helper
# degrades per-dim: a dim that does not tile its mesh axes is simply
# left unconstrained, so the no-mesh / model=1 paths trace the same
# program as ever (DESIGN.md §12).
# ----------------------------------------------------------------------

def model_shard_count(mesh: Optional[Mesh] = None) -> int:
    """How many ways the active mesh splits the flat model dim — the
    size of the ``model`` axis; 1 without a mesh or without the axis,
    so callers can gate model-sharded work on ``> 1``."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None or MODEL_AXIS not in mesh.axis_names:
        return 1
    return mesh.shape[MODEL_AXIS]


def update_spec(ndim: int, axis: int = 0,
                mesh: Optional[Mesh] = None) -> Optional[P]:
    """PartitionSpec for a client-stacked *flattened* quantity: dim
    ``axis`` (clients) over the data axes, the last dim (flat D) over
    ``model``.  For 1-D inputs (a lone (D,) vector — AggState, delta)
    only the model placement applies.  None when the mesh constrains
    neither dim."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None:
        return None
    spec = [None] * ndim
    caxes = _client_axes_in(mesh)
    if caxes and ndim > 1 and axis != ndim - 1:
        spec[axis] = caxes if len(caxes) > 1 else caxes[0]
    if MODEL_AXIS in mesh.axis_names and mesh.shape[MODEL_AXIS] > 1:
        spec[-1] = MODEL_AXIS
    if all(s is None for s in spec):
        return None
    return P(*spec)


def _tiling_spec(x, spec: P, mesh) -> Optional[P]:
    """Drop every spec entry whose dim does not tile its mesh axes; None
    when nothing survives (the degrade-gracefully contract, per-dim)."""
    out, any_named = [], False
    for dim, name in zip(x.shape, spec):
        if name is None:
            out.append(None)
            continue
        names = name if isinstance(name, tuple) else (name,)
        size = 1
        for n in names:
            size *= mesh.shape[n]
        if dim % size != 0:
            out.append(None)
        else:
            out.append(name)
            any_named = True
    return P(*out) if any_named else None


def shard_flat(x):
    """Constrain a flattened model-dim quantity over the ``model`` axis —
    for the O(D) streaming AggState, the round delta, and the root
    update.  Two layouts: a rank-1 ``(D,)`` vector tiles its last dim
    (the legacy contract), while the rank-2 **blocked** layout
    ``(ms, L)`` built by :func:`ravel_sharded` places ``model`` on the
    row dim and leaves the column dim unsharded.  No-op without a mesh,
    with a trivial model axis, or when the dim does not tile."""
    mesh = get_mesh()
    if mesh is None or model_shard_count(mesh) <= 1:
        return x
    if x.ndim == 2 and x.shape[0] == model_shard_count(mesh):
        spec = P(MODEL_AXIS, None)
    else:
        spec = _tiling_spec(
            x, P(*([None] * (x.ndim - 1) + [MODEL_AXIS])), mesh)
    if spec is None:
        return x
    am = jax.sharding.get_abstract_mesh()
    if not am.empty and MODEL_AXIS in am.axis_names:
        return jax.lax.with_sharding_constraint(x, NamedSharding(am, spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def shard_updates(x, axis: int = 0):
    """Constrain a flattened client-stacked matrix over *both* mesh
    families: dim ``axis`` (clients) on the data axes AND the last dim
    (flat D) on ``model`` — :func:`shard_clients` composed with
    :func:`shard_flat` as ONE constraint (two sequential constraints
    would each override the other's spec).  Per-dim degrade: either
    placement drops independently when its dim does not tile, and with
    no model axis this is exactly ``shard_clients``."""
    mesh = get_mesh()
    if mesh is None:
        return x
    ms = model_shard_count(mesh)
    if x.ndim == 3 and ms > 1 and x.shape[1] == ms:
        # blocked layout (clients, ms, L) from flatten_updates_sharded:
        # model on the row dim, columns unsharded.
        caxes = _client_axes_in(mesh)
        csize = 1
        for a in caxes:
            csize *= mesh.shape[a]
        cspec = None
        if caxes and x.shape[0] % csize == 0:
            cspec = caxes if len(caxes) > 1 else caxes[0]
        spec = P(cspec, MODEL_AXIS, None)
    else:
        spec = update_spec(x.ndim, axis, mesh)
        if spec is None:
            return x
        spec = _tiling_spec(x, spec, mesh)
        if spec is None:
            return x
    am = jax.sharding.get_abstract_mesh()
    if (not am.empty
            and all(n in am.axis_names for e in spec if e is not None
                    for n in (e if isinstance(e, tuple) else (e,)))):
        return jax.lax.with_sharding_constraint(x, NamedSharding(am, spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def _leaf_plan(path, shape, ms: int):
    """``(shape, size, cols, split_dim)`` for one leaf of the blocked
    layout.  ``split_dim`` is the dim the MODEL_AXIS partition table
    shards for this leaf (when it tiles ``ms``) — rows then follow the
    device tiling, so the blocked build is shard-local; ``None`` picks
    the row-major pad-and-split fallback for replicated leaves."""
    import math as _math
    key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                   for p in path)
    sz = int(_math.prod(shape))
    for k, name in enumerate(param_partition_spec(key, len(shape))):
        if name == MODEL_AXIS and shape[k] % ms == 0:
            return shape, sz, sz // ms, k
    return shape, sz, -(-sz // ms), None


def flatten_updates_sharded(updates):
    """Model-sharded twin of ``core.aggregators.flatten_updates``: the
    same per-element fp32 casts in the same leaf order, but laid out as
    a **shard-aligned blocked matrix** ``(N, ms, L)`` instead of the
    flat ``(N, D)`` — ``ms = model_shard_count()`` rows, ``L = Σ_ℓ
    ceil(n_ℓ/ms)`` columns, sharded ``P(data, model, None)``.

    Why not just tile ``(N, D)`` over ``model``?  GSPMD cannot run a
    concatenate shard-local when the output is sharded along the
    concatenated dim (leaf boundaries don't align with shard
    boundaries), and it all-gathers every ``dynamic_update_slice``
    along a sharded dim — either build materializes the full unsharded
    D as an XLA temp (~400 MB per buffer at 100M params).  The blocked
    layout concatenates along the *unsharded* column dim: each leaf is
    raveled, zero-padded to a multiple of ``ms``, folded into ``ms``
    rows, and the concat runs shard-local while the per-leaf reshape
    lowers to one slice per shard.  Peak extra memory is one leaf, not
    D (DESIGN.md §12).

    Row assignment is **tiling-aligned**: a leaf whose partition-table
    spec shards dim ``k`` over ``model`` is split along dim ``k`` into
    its ``ms`` device tiles — row ``s`` holds exactly the elements
    device ``s`` already owns, so building the blocked matrix from
    tensor-sharded gradients is a pure local reshape (no per-leaf
    all-gather).  Unsharded leaves (biases, norms, non-tiling dims)
    fall back to a row-major split of the raveled leaf, zero-padded to
    a multiple of ``ms`` — they are the small ones.

    Element values are bitwise those of the flat build modulo
    arrangement (padding elements are zeros that never reach the model:
    ``unravel`` trims them).  Callers gate on ``model_shard_count() >
    1``, so the trivial-model-axis jaxpr stays byte-identical to the
    historical flat path."""
    ms = model_shard_count()
    flat_p, treedef = jax.tree_util.tree_flatten_with_path(updates)
    plans = [_leaf_plan(path, u.shape[1:], ms) for path, u in flat_p]
    leaves = [u for _, u in flat_p]
    n = leaves[0].shape[0]

    pieces = []
    with jax.named_scope("flatten"):
        for u, (shape, sz, c, k) in zip(leaves, plans):
            uf = u.astype(jnp.float32)
            if k is not None:
                nk = shape[k]
                uf = uf.reshape((n,) + shape[:k] + (ms, nk // ms)
                                + shape[k + 1:])
                uf = jnp.moveaxis(uf, 1 + k, 1)
                pieces.append(uf.reshape(n, ms, c))
            else:
                p = uf.reshape(n, sz)
                if c * ms != sz:
                    p = jnp.pad(p, ((0, 0), (0, c * ms - sz)))
                pieces.append(p.reshape(n, ms, c))
        flat = shard_updates(jnp.concatenate(pieces, axis=2))

    def unravel(vec):
        # vec: (ms, L) — slice each leaf's column band and invert its
        # row assignment (tile order for sharded leaves, row-major +
        # pad trim for the rest).
        outs, o = [], 0
        for shape, sz, c, k in plans:
            band = vec[:, o:o + c]
            if k is not None:
                nk = shape[k]
                band = band.reshape((ms,) + shape[:k] + (nk // ms,)
                                    + shape[k + 1:])
                band = jnp.moveaxis(band, 0, k)
                outs.append(band.reshape(shape))
            else:
                outs.append(band.reshape(ms * c)[:sz].reshape(shape))
            o += c
        return jax.tree.unflatten(treedef, outs)
    return flat, unravel


def ravel_sharded(tree):
    """One-client :func:`flatten_updates_sharded`: ravel a pytree into
    the blocked ``(ms, L)`` fp32 layout, sharded ``P(model, None)`` —
    the enclave's per-guide flattening and the fltrust root at zoo
    scale.  Same column offsets and row assignment as the
    client-stacked builder, so guides and updates align
    element-for-element."""
    ms = model_shard_count()
    flat_p, _ = jax.tree_util.tree_flatten_with_path(tree)
    pieces = []
    with jax.named_scope("flatten"):
        for path, u in flat_p:
            shape, sz, c, k = _leaf_plan(path, u.shape, ms)
            uf = u.astype(jnp.float32)
            if k is not None:
                nk = shape[k]
                uf = uf.reshape(shape[:k] + (ms, nk // ms) + shape[k + 1:])
                uf = jnp.moveaxis(uf, k, 0)
                pieces.append(uf.reshape(ms, c))
            else:
                p = uf.reshape(sz)
                if c * ms != sz:
                    p = jnp.pad(p, (0, c * ms - sz))
                pieces.append(p.reshape(ms, c))
        return shard_flat(jnp.concatenate(pieces, axis=1))


# ----------------------------------------------------------------------
# Parameter partition rules (megatron-style + expert parallel).
# Keyed on substrings of the flattened parameter path.
# ----------------------------------------------------------------------
_RULES = (
    # (path substring, spec builder(ndim))
    ("embed",          lambda nd: _last(nd, None, over_first=True)),   # (V, D): shard V
    ("lm_head",        lambda nd: _last(nd, MODEL_AXIS)),              # (D, V): shard V
    ("wq",             lambda nd: _last(nd, MODEL_AXIS)),              # (D, H*dh)
    ("wk",             lambda nd: _last(nd, MODEL_AXIS)),
    ("wv",             lambda nd: _last(nd, MODEL_AXIS)),
    ("wo",             lambda nd: _secondlast(nd, MODEL_AXIS)),        # (H*dh, D)
    ("w_up",           lambda nd: _last(nd, MODEL_AXIS)),              # (D, F)
    ("w_gate",         lambda nd: _last(nd, MODEL_AXIS)),
    ("w_down",         lambda nd: _secondlast(nd, MODEL_AXIS)),        # (F, D)
    ("router",         lambda nd: _last(nd, None)),
    ("routed",         lambda nd: _expert(nd)),                        # (..., E, D, F): shard E
    ("shared",         lambda nd: _last(nd, MODEL_AXIS)),
    ("in_proj",        lambda nd: _last(nd, MODEL_AXIS)),              # mamba (D, 2*d_inner)
    ("conv_w",         lambda nd: _last(nd, MODEL_AXIS)),              # (k, d_inner)
    ("conv_b",         lambda nd: _last(nd, MODEL_AXIS)),
    ("x_proj",         lambda nd: _secondlast(nd, MODEL_AXIS)),        # (d_inner, R+2S)
    ("dt_proj",        lambda nd: _last(nd, MODEL_AXIS)),              # (R, d_inner)
    ("A_log",          lambda nd: _secondlast(nd, MODEL_AXIS)),        # (d_inner, S)
    ("D_skip",         lambda nd: _last(nd, MODEL_AXIS)),              # (d_inner,)
    ("dt_bias",        lambda nd: _last(nd, MODEL_AXIS)),
    ("out_proj",       lambda nd: _secondlast(nd, MODEL_AXIS)),        # (d_inner, D)
)


def _last(nd, axis, over_first=False):
    spec = [None] * nd
    if over_first:
        spec[-2 if nd >= 2 else 0] = MODEL_AXIS   # embed (.., V, D) -> shard V
    else:
        spec[-1] = axis
    return P(*spec)


def _secondlast(nd, axis):
    spec = [None] * nd
    if nd >= 2:
        spec[-2] = axis
    else:
        spec[-1] = axis
    return P(*spec)


def _expert(nd):
    # routed expert weights are (n_groups?, E, D, F) — shard the expert dim.
    spec = [None] * nd
    spec[-3 if nd >= 3 else 0] = MODEL_AXIS
    return P(*spec)


def param_partition_spec(path: str, ndim: int) -> P:
    for key, builder in _RULES:
        if key in path:
            return builder(ndim)
    return P()  # norms, biases, scalars: replicated


def partition_pytree(params):
    """Map a parameter pytree to a pytree of PartitionSpecs."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = []
    for path, leaf in flat:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
        specs.append(param_partition_spec(key, leaf.ndim))
    return jax.tree_util.tree_unflatten(treedef, specs)


def param_shardings(params, mesh: Optional[Mesh] = None):
    """NamedSharding pytree for a zoo parameter pytree on the client x
    model mesh: each leaf takes its ``_RULES`` MODEL_AXIS placement and
    is *replicated* over the client (pod, data) axes — every client
    trains the same parameters; only tensor parallelism splits them.
    Leaves whose named dim does not tile the model axis degrade to
    replicated (same per-dim contract as :func:`shard`).  None without
    a mesh."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None:
        return None
    specs = partition_pytree(params)

    def one(leaf, spec):
        s = _tiling_spec(leaf, spec, mesh) if spec else None
        return NamedSharding(mesh, s if s is not None else P())
    return jax.tree.map(one, params, specs)


def place_params(params, mesh: Optional[Mesh] = None):
    """Eagerly place a parameter pytree with :func:`param_shardings` —
    the one host->device scatter a model-sharded run performs, before
    the compiled segments take over.  No-op without a mesh or with a
    trivial model axis (replicated placement would change nothing the
    engine's constraints don't already pin)."""
    mesh = mesh if mesh is not None else get_mesh()
    if mesh is None or model_shard_count(mesh) <= 1:
        return params
    return jax.device_put(params, param_shardings(params, mesh))


def shard_params(params):
    """Traced twin of :func:`place_params`: per-leaf sharding
    constraints inside the compiled round body, so the updated
    parameters keep their tensor-parallel layout through the scan carry
    instead of drifting to whatever layout the unravel slice produces.
    No-op without a mesh or with a trivial model axis."""
    mesh = get_mesh()
    if mesh is None or model_shard_count(mesh) <= 1:
        return params
    specs = partition_pytree(params)

    def one(leaf, spec):
        s = _tiling_spec(leaf, spec, mesh) if spec else None
        if s is None:
            return leaf
        return jax.lax.with_sharding_constraint(leaf, NamedSharding(mesh, s))
    return jax.tree.map(one, params, specs)
