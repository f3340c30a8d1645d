"""GSPMD sharding rules: logical model axes -> 5-D mesh axes.

This module *is* the TPU replacement for the reference's FSDP2 wrapping
(model_factory.py:168-246) and DTensor TP plan (model_factory.py:657-766): instead of
wrapper modules that intercept forwards, every parameter/activation carries a logical
axis name and these rules lower them to mesh PartitionSpecs. XLA then inserts the
all-gathers/reduce-scatters FSDP2 does manually, and the all-reduces of the rowwise/
colwise TP plan.

Default rule set (reference parity):
- FSDP (dp_shard): every parameter's largest non-TP dim sharded over dp_shard —
  expressed by mapping "embed" (for 2D+ weights) onto dp_shard when tp is unused, or
  combined (dp_shard,) with tp on separate axes.
- TP: q/k/v + W/V/c_fc colwise => "heads"/"kv_heads"/"mlp" on tp; c_proj/W_2 rowwise
  (input sharded) — same effective layout as the reference plan; embedding/lm_head on
  "vocab" over tp (vocab-parallel lookup + XLA-inserted psum).
- SP: the residual stream's rows are "seq_sp", split over (cp, tp) between a block's products, as
  SequenceParallel in the reference plan (`gather_seq` / `scatter_seq`, at the end of this file);
  batch is sharded over (dp_replicate, dp_shard), and "seq", the rows inside a product, over cp alone.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from modalities_tpu.running_env.device_mesh import DeviceMeshHandle

LogicalRules = tuple[tuple[str, Optional[str | tuple[str, ...]]], ...]


def default_logical_axis_rules(mesh_handle: DeviceMeshHandle) -> LogicalRules:
    axis_names = mesh_handle.axis_names
    has = lambda n: n in axis_names and mesh_handle.degrees.get(n, 1) > 1  # noqa: E731

    tp = "tp" if has("tp") else None
    dp_shard = "dp_shard" if "dp_shard" in axis_names else None
    cp = "cp" if has("cp") else None
    pp = "pp" if has("pp") else None

    # deliberately WITHOUT dcn: on a multi-slice mesh the train/eval steps run the
    # model under jax.vmap(..., spmd_axis_name="dcn") over per-slice batch groups,
    # and vmap prepends dcn onto every in-model sharding constraint itself — listing
    # it here would double-assign the axis inside the vmapped region
    batch_axes = tuple(n for n in ("dp_replicate", "dp_shard") if n in axis_names)

    rules: list[tuple[str, Optional[str | tuple[str, ...]]]] = [
        ("batch", batch_axes if batch_axes else None),
        # "seq": the rows INSIDE a mixer or an MLP, whole over tp (its heads / hidden split there), over cp alone;
        # "seq_sp": the rows of the residual stream and of the norms BETWEEN the products, over cp then tp
        ("seq", cp),
        ("seq_sp", tuple(a for a in (cp, tp) if a) or None),
        # parameters: FSDP over dp_shard on the "embed" dim, TP on head/mlp/vocab dims
        ("embed", dp_shard),
        ("heads", tp),
        ("kv_heads", tp),
        ("head_dim", None),
        ("mlp", tp),
        # an expert layer's stacks [experts held, embed, expert_mlp]: the experts' axis waits for an `ep` mesh axis
        # and its exchange, so every chip of a mesh holds the same experts; an expert's hidden dim splits over tp as
        # the dense one does. The latent of latent attention and the router's outputs are small and replicated.
        ("experts", None),
        ("expert_mlp", tp),
        ("latent", None),
        ("router", None),
        ("vocab", tp),
        # LOGITS vocab dim: sharded over tp only when loss parallelism is enabled —
        # the CE logsumexp/gather then runs on vocab shards with XLA-inserted psums
        # (the reference lists loss parallel as "planned"; here it is one rule).
        # Disabled: logits replicate over tp before the loss (DTensor-redistribute
        # equivalent).
        ("vocab_logits", tp if getattr(mesh_handle, "enable_loss_parallel", False) else None),
        ("seq_param", None),
        # stacked-block scan axis: sharded over pp so each stage group owns its layers'
        # params (the GSPMD expression of stage-wise parameter placement; the shard_map
        # GPipe schedule in parallel/pipeline.py consumes the same layout)
        ("layers", pp),
    ]
    return tuple(rules)


def logical_to_mesh_spec(logical_axes, rules: LogicalRules) -> P:
    """Map a tuple of logical axis names to a PartitionSpec via the rule list."""
    table = dict(rules)
    spec = []
    used: set[str] = set()
    for ax in logical_axes:
        target = table.get(ax)
        if target is None:
            spec.append(None)
            continue
        targets = target if isinstance(target, tuple) else (target,)
        free = tuple(t for t in targets if t not in used)
        used.update(free)
        if not free:
            spec.append(None)
        elif len(free) == 1:
            spec.append(free[0])
        else:
            spec.append(free)
    return P(*spec)


def fit_spec_to_shape(spec: P, shape: tuple, mesh: Mesh) -> P:
    """`spec` without the mesh axes that do not divide the dim they would split: that
    dim is replicated instead. One key/value head under tp 2 (multi-query attention, 20
    query heads on 1) cannot be split; the rules say where an axis may go, not that it fits."""
    fitted = []
    for dim, entry in zip(shape, tuple(spec) + (None,) * (len(shape) - len(spec))):
        names = () if entry is None else entry if isinstance(entry, tuple) else (entry,)
        fitted.append(entry if dim % int(np.prod([mesh.shape[n] for n in names], dtype=np.int64)) == 0 else None)
    return P(*fitted)


def params_shardings(abstract_params, rules: LogicalRules, mesh: Mesh):
    """NamedShardings for a pytree of flax Partitioned leaves (from module.init metadata)."""
    import flax

    logical_specs = flax.linen.get_partition_spec(abstract_params)

    def to_named(spec):
        if isinstance(spec, P):
            mesh_spec = logical_to_mesh_spec(tuple(spec), rules)
        else:
            mesh_spec = P()
        return NamedSharding(mesh, mesh_spec)

    return jax.tree.map(to_named, logical_specs, is_leaf=lambda x: isinstance(x, P))


# ------------------------------------------------------------------ activations
# Thread-local activation-constraint rules. flax's global `axis_rules` context also
# affects param machinery (its apply-time shape validation re-runs boxed initializers
# and crashes on DenseGeneral's flat-kernel init under active rules), so activation
# hints use this independent channel: the train step installs the rules, and
# `constrain_activation` lowers logical axes to lax.with_sharding_constraint.

import threading

_ACTIVATION_RULES = threading.local()


class activation_rules:
    """Context manager installing (rules, mesh) for activation constraints. The
    concrete mesh must be carried here: the legacy `with mesh:` context does NOT
    populate jax.sharding.get_abstract_mesh() under jax.jit tracing."""

    def __init__(self, rules: LogicalRules, mesh: Mesh):
        self.rules = rules
        self.mesh = mesh

    def __enter__(self):
        self._prev = getattr(_ACTIVATION_RULES, "state", None)
        _ACTIVATION_RULES.state = (self.rules, self.mesh)
        return self

    def __exit__(self, *exc):
        _ACTIVATION_RULES.state = self._prev
        return False


def constrain_activation(x, logical_axes, explicit: bool = False):
    """Apply a sharding constraint for logical axis names, if rules are installed;
    no-op inside manual shard_map regions (pp/cp) and outside any rules context.
    `explicit=True` applies the constraint even when every dim resolves to None —
    an explicit "replicated here" directive to GSPMD (used to force the FSDP
    all-gather of the embedding table BEFORE the token lookup, so the gather's
    output never carries the table's sharding)."""
    state = getattr(_ACTIVATION_RULES, "state", None)
    if not state:
        return x
    rules, mesh = state
    from modalities_tpu.parallel.jax_compat import manual_axes

    if manual_axes():
        return x
    spec = logical_to_mesh_spec(tuple(logical_axes), rules)
    if not explicit and all(s is None for s in spec):
        return x
    try:
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))
    except ValueError:
        return x


def shard_shape(shape: tuple, logical_axes: tuple) -> tuple[int, ...]:
    """`shape` as one shard holds it under the rules and mesh the step installed (a mesh axis
    that does not divide a dim leaves it whole, as `fit_spec_to_shape` has it); `shape` itself
    with no rules installed or inside a manual region, where values are per shard already. For
    code that plans by what a device will run, which under GSPMD it cannot see while tracing."""
    from modalities_tpu.parallel.jax_compat import manual_axes

    state = getattr(_ACTIVATION_RULES, "state", None)
    if not state or manual_axes():
        return tuple(shape)
    rules, mesh = state
    spec = fit_spec_to_shape(logical_to_mesh_spec(tuple(logical_axes), rules), shape, mesh)
    shards = lambda entry: int(np.prod([mesh.shape[n] for n in (entry if isinstance(entry, tuple) else (entry,))]))  # noqa: E731
    return tuple(dim if entry is None else dim // shards(entry) for dim, entry in zip(shape, spec))


def per_shard(fn, in_logical, out_logical):
    """`fn(axes, *arrays)` run once per shard of the ambient mesh.

    GSPMD cannot partition a Mosaic kernel: on a TPU, a `pallas_call` traced under
    a multi-device `jit` is refused at lowering ("Mosaic kernels cannot be
    automatically partitioned. Please wrap the call in a shard_map"), and inside a
    `shard_map` every mesh axis has to be manual. The kernels on the train path are
    independent per row or per (batch, head), so each dispatcher names its
    operands' dims with logical axes and this runs the kernel under a fully manual
    `shard_map` over the mesh the step installed with `activation_rules`. `axes`
    are the mesh axes the operands ended up split over, for a wrapper that has to
    `psum` a partial result; an operand with no axis on a mesh axis is gathered.

    A mesh axis that does not divide every dim it would split is left out of the
    call, and the kernel runs replicated over it: q and kv heads must split
    together or not at all, and a decode step's one-token sequence cannot split.
    With no rules installed, one device, or an enclosing manual region (pp, cp),
    `fn` runs as it is.
    """

    def call(*arrays):
        state = getattr(_ACTIVATION_RULES, "state", None)
        from modalities_tpu.parallel.jax_compat import manual_axes, shard_map

        if not state or state[1].size == 1 or manual_axes():
            return fn((), *arrays)
        rules, mesh = state

        def names(entry) -> tuple[str, ...]:
            return () if entry is None else entry if isinstance(entry, tuple) else (entry,)

        in_specs = [logical_to_mesh_spec(tuple(axes), rules) for axes in in_logical]
        unusable = {
            name
            for spec, array in zip(in_specs, arrays)
            for dim, entry in zip(array.shape, spec)
            if dim % int(np.prod([mesh.shape[n] for n in names(entry)], dtype=np.int64))
            for name in names(entry)
        }

        def keep(spec: P) -> P:
            kept = [tuple(n for n in names(entry) if n not in unusable) for entry in spec]
            return P(*(k[0] if len(k) == 1 else (k or None) for k in kept))

        in_specs = tuple(keep(spec) for spec in in_specs)
        split_over = tuple(
            n for n in mesh.axis_names if any(n in names(e) for spec in in_specs for e in spec)
        )
        out_specs = jax.tree.map(
            lambda axes: keep(logical_to_mesh_spec(tuple(axes), rules)),
            out_logical,
            is_leaf=lambda x: isinstance(x, tuple) and all(a is None or isinstance(a, str) for a in x),
        )
        return shard_map(
            lambda *local: fn(split_over, *local),
            mesh=mesh,
            in_specs=in_specs,
            out_specs=out_specs,
            axis_names=frozenset(mesh.axis_names),
        )(*arrays)

    return call


# ------------------------------------------------------------ ZeRO optimizer state
# Cross-replica sharding of the weight update (arXiv 2004.13336, ZeRO-1 semantics):
# every dp_replicate replica holding a full copy of the Adam moments is pure waste —
# the moments are only read/written inside `tx.update`. Expressed GSPMD-style: the
# moment leaves (and the grads feeding them) get the replica axis added onto their
# largest divisible non-model-parallel dim, XLA lowers the grad reduction into a
# reduce-scatter over dp_replicate and re-materializes updated params with an
# all-gather (SimpleFSDP, arXiv 2411.00284, does the same through the partitioner).

ZERO_REPLICA_AXIS = "dp_replicate"
# axes carrying model parallelism: adding the replica axis to a dim they shard would
# entangle the update layout with TP/CP/PP resharding — never candidates. "dcn" is
# listed for the same reason with sharper stakes: optimizer state sharded across
# slices would put the (slow) cross-slice fabric inside every tx.update — ZeRO leaf
# specs must NEVER carry dcn (params/moments replicate across slices; only the
# once-per-step accumulated-grad reduction crosses DCN).
_MODEL_PARALLEL_AXES = frozenset({"tp", "cp", "pp", "dcn"})


def zero_partition_spec(
    shape: tuple[int, ...],
    param_spec: P,
    mesh: Mesh,
    replica_axis: str = ZERO_REPLICA_AXIS,
) -> P:
    """ZeRO spec for one moment/grad leaf: the param spec with `replica_axis`
    prepended onto the largest divisible dim not sharded over a model-parallel axis
    (so a dim already carrying dp_shard becomes ``(dp_replicate, dp_shard)``).
    Leaves with no divisible dim keep the param spec — they stay replicated across
    dp_replicate, which is always correct, just not smaller."""
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    replica_size = axis_sizes.get(replica_axis, 1)
    if replica_size <= 1:
        return param_spec
    entries = list(param_spec) + [None] * (len(shape) - len(param_spec))

    def axes_of(entry) -> tuple[str, ...]:
        if entry is None:
            return ()
        return entry if isinstance(entry, tuple) else (entry,)

    if any(replica_axis in axes_of(e) for e in entries):
        return param_spec  # already sharded over the replica axis

    best = None  # (dim size, carries dp_shard, -index) — largest wins, dp_shard breaks ties
    for i, dim in enumerate(shape):
        axes = axes_of(entries[i])
        if any(a in _MODEL_PARALLEL_AXES for a in axes):
            continue
        factor = int(np.prod([axis_sizes[a] for a in axes])) if axes else 1
        if dim % (factor * replica_size) != 0:
            continue
        key = (dim, "dp_shard" in axes, -i)
        if best is None or key > best[0]:
            best = (key, i)
    if best is None:
        return param_spec
    i = best[1]
    existing = axes_of(entries[i])
    entries[i] = (replica_axis, *existing) if existing else replica_axis
    return P(*entries)


def zero_params_shardings(
    abstract_params,
    param_shardings,
    mesh_handle: DeviceMeshHandle,
    replica_axis: str = ZERO_REPLICA_AXIS,
):
    """Param-tree of NamedShardings for ZeRO-sharded grads/moments: each leaf's
    param sharding widened by `zero_partition_spec`. Shapes come from the abstract
    param tree (divisibility is a shape property, not a spec property)."""
    mesh = mesh_handle.mesh

    def one(leaf, sharding):
        return NamedSharding(
            mesh, zero_partition_spec(tuple(leaf.shape), sharding.spec, mesh, replica_axis)
        )

    return jax.tree.map(one, abstract_params, param_shardings)


def batch_sharding(mesh_handle: DeviceMeshHandle) -> NamedSharding:
    """Global batch: batch dim over (dcn, dp_replicate, dp_shard), seq dim over cp.

    dcn leads: on a multi-slice mesh each slice owns one contiguous block of the
    global batch, so the per-slice training compute (train_step's vmap over dcn
    groups) touches only resident rows — no cross-slice data movement."""
    axis_names = mesh_handle.axis_names
    batch_axes = tuple(n for n in ("dcn", "dp_replicate", "dp_shard") if n in axis_names)
    cp = "cp" if "cp" in axis_names and mesh_handle.degrees.get("cp", 1) > 1 else None
    return NamedSharding(mesh_handle.mesh, P(batch_axes if batch_axes else None, cp))


def replicated(mesh_handle: DeviceMeshHandle) -> NamedSharding:
    return NamedSharding(mesh_handle.mesh, P())


def installed_mesh_size() -> int:
    """Devices of the mesh the step installed with `activation_rules`; 1 with none installed. For code that
    has a form for one device only and leaves the other to GSPMD (`ops/expert_dispatch.combine_form`)."""
    state = getattr(_ACTIVATION_RULES, "state", None)
    return int(state[1].size) if state else 1


def installed_axis_size(name: str) -> int:
    """The size of mesh axis `name` in the mesh the step installed with `activation_rules`; 1 with none installed or no such axis."""
    state = getattr(_ACTIVATION_RULES, "state", None)
    return int(state[1].shape.get(name, 1)) if state else 1


# ------------------------------------------------------------ sequence-parallel regions
# Megatron's sequence parallelism (arXiv 2205.05198; the reference's SequenceParallel plan): between a block's
# products the residual stream's rows are split over tp ("seq_sp"), and a region of tensor parallelism (a mixer's or
# an MLP's products, the heads or the hidden split over tp) has two edges. `gather_seq` before its column-parallel
# products: an all-gather of the rows over tp, whose cotangent is reduce-scattered. `scatter_seq` for its row-parallel
# product: the product a shard and a reduce-scatter of the partial sums, whose cotangent is all-gathered. Asked for
# with a sharding constraint alone the partitioner splits the stream and still writes an all-reduce and a slice, and
# the chip's compiler takes a `psum_scatter` over the sequence dimension apart into the same two; scattered over a
# LEADING dimension it stays one `reduce-scatter` instruction (PERF.md, PR 51), so both edges move the tp shards'
# blocks of rows through a leading axis. The regions are manual over the whole mesh, as `per_shard`'s are (a bfloat16
# sum inside a partly manual one trips a check of XLA's on the CPU: parallel/pipeline.py): a weight comes in whole
# on its embed dim, gathered over dp_shard by the partitioner at the region's edge as it was before the product, and
# its gradient leaves summed over the batch's axes.


class SeqRegion(NamedTuple):
    mesh: Mesh
    batch: Optional[tuple[str, ...]]  # what "batch" splits the rows' first dim over
    axes: tuple[str, ...]  # what "seq_sp" splits the rows over: cp where the mesh has one, then tp

    @property
    def cp(self) -> Optional[tuple[str, ...]]:
        return tuple(a for a in self.axes if a != "tp") or None


def seq_region(batch: int, seq_len: int, *split_over_tp: int) -> Optional[SeqRegion]:
    """The region the step's mesh allows for the products of `batch` rows of `seq_len` whose heads or hidden dims are
    `split_over_tp`, or None: no rules installed, a mesh whose tp is 1, an enclosing manual region (pp, cp), a mesh
    axis that neither "batch" nor "seq_sp" names (dcn), rows that their axes do not divide or a dim that tp does not.
    With None a caller's products are `jax.lax.dot_general` as they stand and the collectives the partitioner's."""
    from modalities_tpu.parallel.jax_compat import manual_axes

    state = getattr(_ACTIVATION_RULES, "state", None)
    if not state or manual_axes():
        return None
    rules, mesh = state
    table = dict(rules)
    over = lambda axes: int(np.prod([mesh.shape[a] for a in axes], dtype=np.int64))  # noqa: E731
    names = lambda entry: () if entry is None else entry if isinstance(entry, tuple) else (entry,)  # noqa: E731
    rows, axes = names(table.get("batch")), names(table.get("seq_sp"))
    if "tp" not in axes or any(size > 1 and name not in rows + axes for name, size in mesh.shape.items()):
        return None
    if batch % over(rows) or seq_len % over(axes) or any(dim % mesh.shape["tp"] for dim in split_over_tp):
        return None
    return SeqRegion(mesh, rows or None, axes)


def _manual(region: SeqRegion, fn, in_specs, out_specs):
    from modalities_tpu.parallel.jax_compat import shard_map

    return shard_map(fn, mesh=region.mesh, in_specs=in_specs, out_specs=out_specs, axis_names=frozenset(region.mesh.axis_names))


def gather_seq(region: SeqRegion, x):
    """`x` [B, S, E], rows split over "seq_sp" -> [tp, B, S, E], the leading axis split over tp: every tp shard's own
    copy of the rows (of its cp shard's), for `column_product`. A copy a shard and not one replicated array, so that
    the cotangent is a partial sum a shard, which this function's transpose reduce-scatters: once for all the
    products that read the copy. Wrap the gather and its products in one `jax.checkpoint` and the backward gathers
    again from the split rows where it would keep the copy."""
    tp = region.mesh.shape["tp"]

    def gather(rows):  # [b, s, E] -> [1, b, tp * s, E]
        b, s, e = rows.shape
        return jax.lax.all_gather(rows, "tp", axis=0, tiled=False).transpose(1, 0, 2, 3).reshape(1, b, tp * s, e)

    return _manual(region, gather, P(region.batch, region.axes, None), P("tp", region.batch, region.cp, None))(x)


def column_product(region: SeqRegion):
    """A `dot_general` (what `flax.linen.DenseGeneral(dot_general=...)` takes) for a column-parallel product of
    `gather_seq`'s copies [tp, B, S, E] with a kernel [E, features...] whose first feature dim is split over tp:
    [1, B, S, features...], each shard's features from its own copy, so the copies' axis comes out as one (what a bias
    broadcasts against; the caller drops it)."""

    def dot_general(lhs, rhs, dimension_numbers, precision=None, preferred_element_type=None):
        if dimension_numbers != (((lhs.ndim - 1,), (0,)), ((), ())):
            raise ValueError(f"a column-parallel product contracts the copies' last dim with the kernel's first, not {dimension_numbers}")

        def product(copy, kernel):
            return jax.lax.dot_general(copy, kernel, dimension_numbers, precision=precision, preferred_element_type=preferred_element_type)

        in_specs = (P("tp", region.batch, region.cp, None), P(None, "tp"))
        return _manual(region, product, in_specs, P(None, region.batch, region.cp, "tp"))(lhs, rhs)

    return dot_general


def scatter_seq(region: SeqRegion):
    """A `dot_general` for a row-parallel product and the region's closing edge: `lhs` [B, S, features...] contracted
    over its features, the first of them split over tp, with a kernel [features..., E]; the shards' partial sums are
    reduce-scattered over tp, so the result [B, S, E] comes out with its rows split over "seq_sp" (a bias is added
    after, by the caller, as it is after the partitioner's sum)."""
    tp = region.mesh.shape["tp"]

    def dot_general(lhs, rhs, dimension_numbers, precision=None, preferred_element_type=None):
        contracted = tuple(range(2, lhs.ndim))
        if dimension_numbers != ((contracted, tuple(range(len(contracted)))), ((), ())):
            raise ValueError(f"a row-parallel product contracts all of [B, S, features...]'s features with the kernel's first dims, not {dimension_numbers}")

        def product(rows, kernel):
            partial = jax.lax.dot_general(rows, kernel, dimension_numbers, precision=precision, preferred_element_type=preferred_element_type)
            b, s, e = partial.shape
            blocks = partial.reshape(b, tp, s // tp, e).transpose(1, 0, 2, 3)  # a tp shard's block of rows along a leading axis
            return jax.lax.psum_scatter(blocks, "tp", scatter_dimension=0, tiled=False)

        in_specs = (P(region.batch, region.cp, "tp"), P("tp"))
        return _manual(region, product, in_specs, P(region.batch, region.axes, None))(lhs, rhs)

    return dot_general
