"""Traffic of kind ``collective``: a closed loop of ``Communicator`` calls
over the configuration's hypercube, as an eager caller makes them.

Every (primitive, dim selection, message size) shape gets one jitted
``shard_map`` of the program's call with ``algorithm="auto"`` and one
seeded integer-valued float32 input, both made and warmed in set-up. The
window issues rounds of every shape once, in an order drawn from the seed,
and blocks on each result before the next call. One output per (primitive,
selection), at a size drawn from the seed and at a call drawn by reservoir
sampling, is kept and compared with the reference after the window.
"""
from __future__ import annotations

import gc
import itertools

import numpy as np

from bench import flops, harness, weights

VALUE_BOUND = 2 ** 20      # |payload| < 2**20: a sum of 4 is exact in fp32


def program_call(comm, primitive: str, v, nd: int):
    """The program's collective on a per-PE block (leading cube axes of
    size 1, then rows, cols)."""
    if primitive == "all_reduce":
        return comm.all_reduce(v, algorithm="auto")
    if primitive == "reduce_scatter":
        return comm.reduce_scatter(v, axis=nd + 1, algorithm="auto")
    if primitive == "all_gather":
        return comm.all_gather(v, axis=nd, algorithm="auto")
    if primitive == "all_to_all":
        return comm.all_to_all(v, split_axis=nd + 1, concat_axis=nd + 1,
                               algorithm="auto")
    raise ValueError(primitive)


# what the window calls: tests and the control put another path in its place
CALL = program_call


def shapes(tr: dict):
    return list(itertools.product(tr["primitives"], tr["selections"],
                                  tr["message_mib"]))


def run(ctx) -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.compat import make_mesh, shard_map
    from repro.core.hypercube import Hypercube

    c, tr, seed = ctx.cell.config, ctx.cell.traffic, ctx.seed
    names = tuple(c["mesh_axes"])
    dims = tuple(c["dims"][n] for n in names)
    nd = len(dims)
    mesh = make_mesh(dims, names, devices=ctx.devices)
    cube = Hypercube.build(mesh, dict(c["dims"]))
    spec = P(*names, None, None)
    sharding = NamedSharding(mesh, spec)
    cols = tr["cols"]
    call = CALL

    base = weights.seed_key(seed)
    gen = {}

    def make_input(k, rows):
        """Shape ``k``'s input; the key is an argument, so one compiled
        program per size serves every seed."""
        if rows not in gen:
            gen[rows] = jax.jit(lambda key, rows=rows: jax.random.randint(
                key, dims + (rows, cols), -VALUE_BOUND + 1, VALUE_BOUND
            ).astype(jnp.float32), out_shardings=sharding)
        return gen[rows](jax.random.fold_in(base, k))

    grid = shapes(tr)
    inputs, fns, bus = {}, {}, {}
    with harness.annotate("bench.setup.inputs"):
        for i, (prim, sel, mib) in enumerate(grid):
            rows = mib * 2 ** 20 // (4 * cols)
            comm = cube.comm(cube.dims_from_bitmap(sel))
            inputs[i] = make_input(i, rows)
            fns[i] = jax.jit(shard_map(
                lambda v, _c=comm, _p=prim: call(_c, _p, v, nd),
                mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False))
            g = comm.group_size
            nbytes = mib * 2 ** 20
            out_bytes = {"all_gather": g * nbytes,
                         "reduce_scatter": nbytes // g}.get(prim, nbytes)
            bus[i] = flops.bus_bytes(prim, g, nbytes, out_bytes)
    ctx.phase("inputs")
    with harness.annotate("bench.setup.warm"):
        for i in fns:
            fns[i](inputs[i]).block_until_ready()

    # which call of each (primitive, selection) is checked: a size drawn
    # from the seed, then reservoir sampling over that shape's calls
    rng = np.random.default_rng([int(seed), 7])
    checked = {}
    for prim, sel in itertools.product(tr["primitives"], tr["selections"]):
        mib = tr["message_mib"][rng.integers(len(tr["message_mib"]))]
        checked[grid.index((prim, sel, mib))] = 0
    kept = {}

    def issue(i):
        out = fns[i](inputs[i])
        out.block_until_ready()
        if i in checked:
            checked[i] += 1
            if rng.random() < 1.0 / checked[i]:
                kept[i] = out
        return bus[i]

    ctx.start_window()
    t0 = harness.now()
    calls, moved = 0, 0.0
    done = False
    while not done:
        for i in rng.permutation(len(grid)):
            moved += issue(int(i))
            calls += 1
            if harness.now() - t0 >= ctx.seconds:
                done = True
                break
    t1 = harness.now()
    ctx.end_window()

    trace, trace_bus = {}, 0.0
    if ctx.trace:
        with harness.traced(trace):
            for _ in range(tr["trace_rounds"]):
                for i in rng.permutation(len(grid)):
                    with harness.annotate(f"bench.call.{grid[int(i)][0]}"):
                        trace_bus += issue(int(i))
    peak = harness.peak_bytes(ctx.devices)

    # the reference, on the host, for every kept output
    ref = ctx.cell.reference
    wrong = []
    for i, out in kept.items():
        prim, sel, mib = grid[i]
        want = ref.call(prim, np.asarray(inputs[i]), sel)
        got = np.asarray(out)
        if got.shape != want.shape or not np.array_equal(got, want):
            wrong.append(f"{prim}[{sel}]@{mib}MiB")
    never = [grid[i] for i in checked if i not in kept]
    ctx.note(f"checked {sorted(grid[i] for i in kept)}; wrong {wrong}; "
             f"never issued {never}")
    del inputs, fns, kept
    gc.collect()
    return {
        "attempted": calls, "failed": 0, "peak_bytes": peak,
        "values": {"collective_busbw": moved / (t1 - t0) / 1e9},
        "rec": {"trace": trace, "trace_bus_bytes": trace_bus},
        "numbers": {"wrong_outputs": len(wrong) + len(never)},
    }
