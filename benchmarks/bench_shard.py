"""Per-step collective traffic of the mesh-native sparse memory path vs the
GSPMD slot-sharded control, from the compiled HLO (launch/hlo_cost.py), on a
forced 8-device host-platform mesh.

The claim under test (docs/sharding.md, the paper's O(K·W) asymptotics at
scale-out): a compiled `sam_step` on the mesh-native path moves O(B·K·W)
collective bytes per step — the (B, H, K) score+index all-gather of the
K-merge plus the (B, H, K, W) winner-row psum — **independent of N**. The
positive control is the pre-mesh-native route (a slot-sharded legacy state
handed to GSPMD, whose dynamically-indexed sweep/gather forces O(N)
collective terms); its bytes must grow with N, or the guard itself is dead.

LSH mode (the sharded ANN index, docs/sharding.md) gets its own rows: the
sharded-index step's collective bytes must stay flat in N and strictly
below the replicated-index positive control (which psum-gathers the full
O(C·W) candidate rows per step), per-device bucket-table bytes must drop
by exactly the shard factor vs the replicated control, and `ann_build` on
a sharded buffer must compile with no O(N·W) all-gather.

The 2D (data × model) lanes compose batch sharding with slot sharding on
meshes carved from the same 8 forced devices: per-device collective bytes
must stay flat in N *and* in global B (growing the batch along the data
axis is free per device), every collective in the compiled step must group
on the model axis only — ``collective_groups`` proves zero data-axis
traffic on the memory path — and a replicated-batch control on the same
2D mesh must pay ~data× more per device.

All properties are asserted here and recorded to
``experiments/bench/BENCH_shard.json``.

Run:  PYTHONPATH=src python -m benchmarks.bench_shard [--quick]
"""
from __future__ import annotations

# CLI runs force the 8-device host platform; this MUST precede any jax
# import (jax locks the device count on first init) and MUST NOT fire for
# mere importers (tests/test_mesh_parity.py borrows the compile helpers
# under its own externally-set XLA_FLAGS — mutating the env at import time
# would silently flip the whole importing process to 8 fake devices).
import os
if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8")

import argparse
import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmarks.common import row
from repro.analysis import lints as analysis_lints
from repro.analysis.envelope import check_growth
from repro.analysis.measure import from_hlo
from repro.core import sam as sam_lib
from repro.core.types import ControllerConfig, MemoryConfig
from repro.distributed import mem_shard
from repro.launch.mesh import make_mesh

OUT_DIR = "experiments/bench"
OUT_PATH = os.path.join(OUT_DIR, "BENCH_shard.json")

B, W, H, K, D = 2, 16, 2, 4, 6
CTL = ControllerConfig(D, 16, D)
SHARDS = 8


def _cfg(num_slots: int) -> sam_lib.SAMConfig:
    return sam_lib.SAMConfig(
        MemoryConfig(num_slots=num_slots, word_size=W, num_heads=H, k=K),
        CTL)


def _lsh_cfg(num_slots: int) -> sam_lib.SAMConfig:
    return sam_lib.SAMConfig(
        MemoryConfig(num_slots=num_slots, word_size=W, num_heads=H, k=K,
                     ann="lsh", lsh_tables=4, lsh_bits=6,
                     lsh_bucket_size=32),
        CTL)


def _collective_record(hlo_text: str, *,
                       buffer_bytes: float | None = None) -> dict:
    """One compiled module -> its collective profile, via the shared
    measurement layer (repro.analysis). ``buffer_bytes`` additionally runs
    the ``full_buffer_collective`` lint against that buffer size and
    records the offenses — the "no collective anywhere near the full
    buffer/table" guard this bench and the mesh parity tests assert."""
    m = from_hlo(hlo_text)
    rec = {
        "collectives": m.coll,
        "bytes_total": m.coll_bytes,
        "moved_total": m.coll_moved,
        "collective_group_sizes": m.group_sizes,
    }
    if buffer_bytes is not None:
        rec["full_buffer_offenses"] = analysis_lints.full_buffer_collective(
            m, {"buffer_bytes": buffer_bytes})
    return rec


def _flat_in(var: str, points, values):
    """Fitted-growth verdict (envelope.GrowthCheck) for a bytes sweep:
    flat (O(1)) within the checker's standard tolerance."""
    sizes = [{var: p} for p in points]
    return check_growth("collective_bytes", None, points, sizes,
                        [float(v) for v in values], 0.1)


def compile_mesh_step(mesh, num_slots: int) -> dict:
    cfg = _cfg(num_slots)
    with mem_shard.memory_mesh(mesh, num_slots):
        params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
        state = mem_shard.place_state(sam_lib.init_state(B, cfg))
        step = jax.jit(lambda p, s, x: sam_lib.sam_step(p, cfg, s, x))
        hlo = step.lower(params, state, jnp.zeros((B, D))).compile().as_text()
    rec = _collective_record(hlo, buffer_bytes=B * num_slots * W * 4)
    rec.update(path="mesh", N=num_slots)
    return rec


def compile_mesh_step_lsh(mesh, num_slots: int, *,
                          index_partitions: int | None = None) -> dict:
    """LSH-mode sharded step. ``index_partitions=None`` builds the index
    ownership-partitioned to the mesh (each device stores 1/S of the
    bucket tables, inserts collective-free, queries merged through the
    O(B·K) all-gather); ``index_partitions=1`` is the retired
    replicated-index path — this bench's positive control: its per-device
    index bytes are S× larger and its reads psum-gather the full
    O(C·W) candidate rows every step."""
    cfg = _lsh_cfg(num_slots)
    with mem_shard.memory_mesh(mesh, num_slots):
        params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
        state = mem_shard.place_state(
            sam_lib.init_state(B, cfg, ann_partitions=index_partitions))
        step = jax.jit(lambda p, s, x: sam_lib.sam_step(p, cfg, s, x))
        hlo = step.lower(params, state, jnp.zeros((B, D))).compile().as_text()
        bucket_dev_bytes = state.ann.buckets.addressable_shards[0].data.nbytes
        index_dev_bytes = bucket_dev_bytes + \
            state.ann.cursor.addressable_shards[0].data.nbytes
        index_total = state.ann.buckets.nbytes + state.ann.cursor.nbytes
    # Guard against the tighter of the two dense payloads: the memory
    # buffer and the full bucket table (partition-invariant total).
    rec = _collective_record(
        hlo, buffer_bytes=min(B * num_slots * W * 4, index_total))
    rec.update(path=("lsh_mesh" if index_partitions is None
                     else "lsh_replicated_index"),
               N=num_slots, bucket_table_bytes_per_device=bucket_dev_bytes,
               index_bytes_per_device=index_dev_bytes,
               index_bytes_total=index_total)
    return rec


def compile_lsh_build(mesh, num_slots: int) -> dict:
    """`ann_build` on a slot-sharded buffer: must compile shard-local —
    no canonical all-gather of the O(N·W) memory (the pre-shard path's
    rebuild all-gathered the whole buffer back to canonical form)."""
    from repro.core import ann as ann_lib
    cfg = _lsh_cfg(num_slots).memory
    with mem_shard.memory_mesh(mesh, num_slots):
        planes = ann_lib.lsh_planes(jax.random.PRNGKey(0), cfg)
        state = mem_shard.place_state(sam_lib.init_state(
            B, _lsh_cfg(num_slots)))
        build = jax.jit(lambda p, m: ann_lib.ann_build(p, m, cfg))
        hlo = build.lower(planes, state.memory).compile().as_text()
    rec = _collective_record(hlo, buffer_bytes=B * num_slots * W * 4)
    rec.update(path="lsh_build", N=num_slots)
    return rec


def _submesh(shape: tuple) -> jax.sharding.Mesh:
    """A ("data", "model") mesh over the first prod(shape) devices — lets
    one forced-8-device process carve both a (1,4) and a (2,4) mesh so the
    2D lanes compare per-device traffic at equal model degree."""
    import numpy as np
    n = shape[0] * shape[1]
    return jax.sharding.Mesh(
        np.array(jax.devices()[:n]).reshape(shape), ("data", "model"))


def compile_mesh_step_2d(mesh, num_slots: int, global_b: int, *,
                         data_parallel: bool = True) -> dict:
    """One `sam_step` compile on a 2D (data × model) mesh.

    ``data_parallel=True`` composes batch sharding with slot sharding:
    every state leaf lands (B over "data", rows over "model"), the input
    batch-sharded to match, so the compiled per-device program sees
    B_local = B/data rows and its collectives group on the model axis
    only. ``data_parallel=False`` is the positive control: the same mesh
    and the same global batch, but memory_mesh built with ``data_axes=()``
    so the batch replicates across the data axis — every device pays the
    full-B score all-gather, ~data× the per-device bytes."""
    cfg = _cfg(num_slots)
    data_axes = ("pod", "data") if data_parallel else ()
    with mem_shard.memory_mesh(mesh, num_slots, data_axes=data_axes):
        ctx = mem_shard.current()
        params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
        state = mem_shard.place_state(sam_lib.init_state(global_b, cfg))
        xspec = P("data") if ctx.data_degree > 1 else P()
        x = jax.device_put(jnp.zeros((global_b, D)),
                           NamedSharding(mesh, xspec))
        step = jax.jit(lambda p, s, x: sam_lib.sam_step(p, cfg, s, x))
        hlo = step.lower(params, state, x).compile().as_text()
    rec = _collective_record(hlo,
                             buffer_bytes=global_b * num_slots * W * 4)
    rec.update(
        path=("mesh2d" if data_parallel else "mesh2d_replicated"),
        N=num_slots, B=global_b,
        data=int(mesh.shape["data"]), model=int(mesh.shape["model"]),
        data_degree=ctx.data_degree)
    return rec


def compile_gspmd_control(mesh, num_slots: int) -> dict:
    """The retired route: legacy (B, N, W) state slot-sharded through
    GSPMD. Kept compilable on purpose — it is this bench's positive
    control for O(N) collective traffic."""
    cfg = _cfg(num_slots)
    params = sam_lib.init_params(jax.random.PRNGKey(0), cfg)
    s = sam_lib.init_state(B, cfg)
    s = s._replace(memory=s.memory[:, :num_slots],
                   last_access=s.last_access[:, :num_slots])
    sh = jax.tree.map(lambda l: NamedSharding(mesh, P()), s)
    sh = sh._replace(memory=NamedSharding(mesh, P(None, "model", None)),
                     last_access=NamedSharding(mesh, P(None, "model")))
    step = jax.jit(lambda p, st, x: sam_lib.sam_step(p, cfg, st, x))
    hlo = step.lower(params, jax.device_put(s, sh),
                     jnp.zeros((B, D))).compile().as_text()
    rec = _collective_record(hlo)
    rec.update(path="gspmd_control", N=num_slots)
    return rec


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller N sweep (CI smoke)")
    args = ap.parse_args(argv)
    sizes = [256, 1024] if args.quick else [256, 1024, 4096]

    mesh = make_mesh((8,), ("model",))
    results = []
    for n in sizes:
        for rec in (compile_mesh_step(mesh, n),
                    compile_gspmd_control(mesh, n),
                    compile_mesh_step_lsh(mesh, n),
                    compile_mesh_step_lsh(mesh, n, index_partitions=1),
                    compile_lsh_build(mesh, n)):
            results.append(rec)
            extra = (f" index {rec['index_bytes_per_device']}B/dev"
                     if "index_bytes_per_device" in rec else "")
            row(f"shard/{rec['path']}/N={n}", 0.0,
                f"{rec['bytes_total']:.0f}B collective{extra}")

    by = {(r["path"], r["N"]): r["bytes_total"] for r in results}
    n_lo, n_hi = sizes[0], sizes[-1]
    mesh_hi = by[("mesh", n_hi)]
    ctrl_hi = by[("gspmd_control", n_hi)]
    # O(B·K·W): mesh-native traffic flat in N (fitted via the shared
    # growth checker), far below the O(N) control, and no single
    # collective anywhere near the full memory buffer (the
    # full_buffer_collective lint, recorded per compile above).
    mesh_fit = _flat_in("N", sizes, [by[("mesh", n)] for n in sizes])
    ctrl_fit = _flat_in("N", sizes, [by[("gspmd_control", n)] for n in sizes])
    row("shard/mesh/N_scaling", 0.0, f"~N^{mesh_fit.exponent:.2f} "
        f"over {n_hi // n_lo}x slots")
    row("shard/control/N_scaling", 0.0, f"~N^{ctrl_fit.exponent:.2f} "
        f"over {n_hi // n_lo}x slots")
    assert mesh_fit.ok, \
        f"mesh collective bytes grew with N: {mesh_fit.values}"
    assert not ctrl_fit.ok, \
        f"positive control did not scale with N: {ctrl_fit.values}"
    assert mesh_hi < ctrl_hi / 4, (mesh_hi, ctrl_hi)
    for r in results:
        if r["path"] == "mesh":
            assert not r["full_buffer_offenses"], \
                f"mesh-path full-buffer collective: {r['full_buffer_offenses']}"

    # LSH mode: sharded-index traffic flat in N and strictly below the
    # replicated-index positive control (which psum-gathers the full
    # O(C·W) candidate rows each step)...
    lsh_fit = _flat_in("N", sizes, [by[("lsh_mesh", n)] for n in sizes])
    row("shard/lsh_mesh/N_scaling", 0.0,
        f"~N^{lsh_fit.exponent:.2f} over {n_hi // n_lo}x slots")
    assert lsh_fit.ok, \
        f"sharded-LSH collective bytes grew with N: {lsh_fit.values}"
    for r in results:
        if r["path"] == "lsh_mesh":
            assert not r["full_buffer_offenses"], \
                f"sharded-LSH full-table collective: " \
                f"{r['full_buffer_offenses']}"
    for n in sizes:
        assert by[("lsh_mesh", n)] < by[("lsh_replicated_index", n)] / 2, \
            (n, by[("lsh_mesh", n)], by[("lsh_replicated_index", n)])
    # ...per-device bucket-table bytes reduced by exactly the shard factor
    # (the replicated-index control carries the whole table per device)...
    idx = {(r["path"], r["N"]): r.get("bucket_table_bytes_per_device")
           for r in results if "bucket_table_bytes_per_device" in r}
    for n in sizes:
        sharded, repl = idx[("lsh_mesh", n)], idx[("lsh_replicated_index", n)]
        row(f"shard/lsh_index_bytes/N={n}", 0.0,
            f"{sharded}B/dev sharded vs {repl}B/dev replicated")
        assert repl == sharded * SHARDS, \
            f"per-device bucket-table bytes not reduced {SHARDS}x: " \
            f"{sharded} vs {repl}"
    assert idx[("lsh_mesh", n_lo)] == idx[("lsh_mesh", n_hi)], \
        "per-device bucket-table bytes must not grow with N"
    # ...and ann_build on a sharded buffer compiles shard-local: no
    # collective anywhere near the O(N·W) memory buffer (the pre-shard
    # rebuild all-gathered the whole thing).
    for r in results:
        if r["path"] == "lsh_build":
            assert not r["full_buffer_offenses"], \
                f"ann_build on a sharded buffer moves a near-full-buffer " \
                f"collective: {r['full_buffer_offenses']}"

    # --- 2D (data × model) composition ------------------------------------
    # Same model degree (4) on both meshes so the per-device comparison is
    # apples-to-apples: (1,4) serves B=2, (2,4) serves global B=4 with
    # B_local=2 per data shard.
    model2d = 4
    mesh14, mesh24 = _submesh((1, model2d)), _submesh((2, model2d))
    for n in sizes:
        for rec in (compile_mesh_step_2d(mesh14, n, B),
                    compile_mesh_step_2d(mesh24, n, 2 * B),
                    compile_mesh_step_2d(mesh24, n, 2 * B,
                                         data_parallel=False)):
            results.append(rec)
            row(f"shard/{rec['path']}/N={n}/B={rec['B']}/data={rec['data']}",
                0.0, f"{rec['bytes_total']:.0f}B collective, groups "
                f"{rec['collective_group_sizes']}")
    by2 = {(r["path"], r["N"], r["B"]): r
           for r in results if r["path"].startswith("mesh2d")}
    d1_hi = by2[("mesh2d", n_hi, B)]
    d2_hi = by2[("mesh2d", n_hi, 2 * B)]
    repl_hi = by2[("mesh2d_replicated", n_hi, 2 * B)]
    # Per-device collective bytes flat in N...
    n_fit = _flat_in("N", sizes,
                     [by2[("mesh2d", n, 2 * B)]["bytes_total"]
                      for n in sizes])
    # ...and flat in global B: doubling B along the data axis must not
    # change what each device moves...
    b_fit = _flat_in("B", [B, 2 * B],
                     [d1_hi["bytes_total"], d2_hi["bytes_total"]])
    row("shard/mesh2d/N_scaling", 0.0,
        f"~N^{n_fit.exponent:.2f} over {n_hi // n_lo}x slots")
    row("shard/mesh2d/B_scaling", 0.0,
        f"~B^{b_fit.exponent:.2f} per-device over 2x global batch "
        f"(replicated control "
        f"{repl_hi['bytes_total'] / max(d2_hi['bytes_total'], 1):.2f}x)")
    assert n_fit.ok, f"2D collective bytes grew with N: {n_fit.values}"
    assert b_fit.ok, \
        f"2D per-device collective bytes grew with global B: {b_fit.values}"
    # ...while the replicated-batch control on the same mesh pays ~data×
    # per device (or the comparison is measuring nothing)...
    assert repl_hi["bytes_total"] >= d2_hi["bytes_total"] * 1.7, \
        f"replicated-batch control not ~2x the 2D lane: " \
        f"{d2_hi['bytes_total']} vs {repl_hi['bytes_total']}"
    # ...and every collective in the 2D step groups on the model axis
    # only — group size == model degree proves zero data-axis collectives
    # on the memory path (a None means an unparsed/global group: dirty).
    for n in sizes:
        gs = by2[("mesh2d", n, 2 * B)]["collective_group_sizes"]
        assert gs == [model2d], \
            f"2D step N={n} has non-model-axis collectives: groups {gs}"

    os.makedirs(OUT_DIR, exist_ok=True)
    record = {
        "bench": "shard",
        "device": jax.devices()[0].platform,
        "devices": jax.device_count(),
        "jax": jax.__version__,
        "shapes": {"B": B, "W": W, "H": H, "K": K},
        "results": results,
    }
    with open(OUT_PATH, "w") as f:
        json.dump(record, f, indent=2)
    print(f"wrote {OUT_PATH} ({len(results)} rows)")
    return record


if __name__ == "__main__":
    main()
