"""Pallas launches inside a program that is sharded over a mesh.

GSPMD partitions XLA operations; it cannot partition a Mosaic custom
call. On a TPU, lowering a jitted program whose operands live on a
multi-device mesh stops at the first kernel with *"Mosaic kernels
cannot be automatically partitioned. Please wrap the call in a
shard_map"* — the engine's train step on more than one chip. (On a
virtual CPU mesh the same kernels run in the Pallas interpreter, which
is ordinary XLA and partitions without complaint, so only a chip or a
TPU export shows it.)

`per_device` is that wrapping, in one place: the launch runs under
`jax.shard_map`, each device on its own block of the batch (and, under
tensor parallelism, of the heads or feed-forward columns), which is how
the engine lays activations out anyway. The mesh is read from the
operands' types — jax carries the abstract mesh in every aval, through
scan, remat and custom-VJP backward rules alike — so callers pass none.
With no mesh, one device, or inside a caller's own shard_map (ring
attention, the pipeline interpreter), the launch is called directly.
"""

import math

import jax
from jax.sharding import PartitionSpec

from deepspeed_tpu.runtime.mesh import DATA_AXIS, EXPERT_AXIS, MODEL_AXIS

# What a dimension of an operand follows. ROWS: the batch, which the
# engine divides over the data axis (and the expert axis, whose devices
# are data-parallel devices too). COLS: heads or feed-forward columns,
# which tensor parallelism divides over the model axis.
ROWS = "rows"
COLS = "cols"


def _mesh_of(operands):
    for x in operands:
        mesh = jax.typeof(x).sharding.mesh
        if not mesh.empty:
            return mesh
    return None


def divides_cols(x):
    """Whether a launch over `x` would divide COLS: the mesh in its type
    has a free model axis wider than one device."""
    mesh = _mesh_of((x,))
    return mesh is not None and MODEL_AXIS in mesh.axis_names and \
        MODEL_AXIS not in mesh.manual_axes and mesh.shape[MODEL_AXIS] > 1


def _axes_dividing(mesh, free, candidates, sizes):
    """The `candidates` that are free mesh axes wider than one device
    and whose product divides every size in `sizes`."""
    axes = [a for a in candidates if a in free and mesh.shape[a] > 1]
    while axes and any(
            s % math.prod(mesh.shape[a] for a in axes) for s in sizes):
        axes.pop()
    return tuple(axes)


def per_device(fn, in_dims, out_dims, row_summed=(), cols=()):
    """`fn` as a function of the same array operands that launches once
    per device of the operands' mesh.

    in_dims / out_dims: for each operand / output, a tuple with one
    entry per dimension: ROWS, COLS or None (held whole by every
    device). row_summed: indices of outputs that are sums over ROWS
    (a bias gradient): each device's partial sum is added over the
    devices that divided the rows. cols: sizes the mesh's columns have
    to divide besides the COLS dimensions themselves (the head count
    behind a [B, T, H·D] operand: a shard holds whole heads, or the
    model axis divides nothing).
    """
    def call(*operands):
        mesh = _mesh_of(operands)
        if mesh is None:
            return fn(*operands)
        free = [a for a in mesh.axis_names if a not in mesh.manual_axes]
        if all(mesh.shape[a] == 1 for a in free):
            return fn(*operands)

        def sizes(tag):
            return [x.shape[i] for x, dims in zip(operands, in_dims)
                    for i, d in enumerate(dims) if d == tag]
        mesh_axes = {
            ROWS: _axes_dividing(mesh, free, (DATA_AXIS, EXPERT_AXIS),
                                 sizes(ROWS)),
            COLS: _axes_dividing(mesh, free, (MODEL_AXIS,),
                                 sizes(COLS) + list(cols)),
        }

        def spec(dims):
            # a tag whose axes did not divide the operand maps to no
            # axis: every device then holds that dimension whole
            return PartitionSpec(*(mesh_axes.get(d) or None for d in dims))

        def local(*blocks):
            outs = fn(*blocks)
            if not mesh_axes[ROWS]:
                return outs
            return tuple(
                jax.lax.psum(o, mesh_axes[ROWS]) if i in row_summed else o
                for i, o in enumerate(outs))

        return jax.shard_map(
            local, mesh=mesh, axis_names=frozenset(free),
            in_specs=tuple(spec(d) for d in in_dims),
            out_specs=tuple(spec(d) for d in out_dims),
            check_vma=False)(*operands)
    return call
