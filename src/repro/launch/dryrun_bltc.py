import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

"""Multi-pod dry-run for the distributed BLTC itself (paper Sec. 3).

Lowers the shard_map SPMD potential step for 256 ranks (one pod, the
"data" axis carries RCB slabs) and 512 ranks (2 pods), using
representative padded shapes for the paper's weak-scaling configuration
(N/rank = 4M, theta = 0.8, n = 8, N_L = N_B = 4000) — lowering needs only
shapes, so no 2-billion-particle tree is built. Reports the same roofline
terms as the LM cells.

  PYTHONPATH=src python -m repro.launch.dryrun_bltc [--multi]
"""
import argparse    # noqa: E402
import json        # noqa: E402
import time        # noqa: E402

import jax         # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import cheby  # noqa: E402
from repro.core import eval as ceval  # noqa: E402
from repro.core.api import TreecodeConfig  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.launch.dryrun import HBM_BW, ICI_BW, PEAK_FLOPS  # noqa: E402
from repro.launch.hlo_analysis import analyze  # noqa: E402
from repro.launch.mesh import auto_mesh  # noqa: E402


def synthetic_shapes(nranks: int, n_per_rank: int, cfg: TreecodeConfig):
    """Representative padded per-rank shapes for a uniform distribution."""
    leaf = cfg.leaf_size
    n1 = cfg.degree + 1
    k3 = n1 ** 3
    nleaves = max(2, int(1.3 * n_per_rank / leaf))
    nnodes = 2 * nleaves + 1
    nbatches = nleaves
    # uniform-cube interaction list widths (measured on small problems,
    # scaled): ~40 approx + ~30 direct per batch at theta=0.8
    a_pad, d_pad = 48, 32
    depth = int(np.ceil(np.log2(max(nleaves, 2)) / 3)) + 2
    f32 = jnp.float32
    i32 = jnp.int32
    shapes = dict(
        src_sorted=((nranks, n_per_rank, 3), f32),
        charges_perm=((nranks, n_per_rank), i32),
        tgt_batched=((nranks, nbatches, leaf, 3), f32),
        gather_index=((nranks, n_per_rank), i32),
        leaf_gather=((nranks, nleaves, leaf), i32),
        node_lo=((nranks, nnodes, 3), f32),
        node_hi=((nranks, nnodes, 3), f32),
        approx_idx=((nranks, nbatches, a_pad), i32),
        direct_idx=((nranks, nbatches, d_pad), i32),
        remote_approx_idx=((nranks, nbatches, 24), i32),
        remote_direct_idx=((nranks, nbatches, 16), i32),
    )
    # per-level buckets: geometric sizes down the tree
    c = 1
    for lvl in range(depth):
        m = min(n_per_rank, max(leaf, n_per_rank // max(c, 1)))
        shapes[f"bucket_gather_{lvl}"] = ((nranks, c, m), i32)
        shapes[f"bucket_nodes_{lvl}"] = ((nranks, c), i32)
        c = min(nnodes, c * 8)
    # two halo rounds (+-1 neighbor), 8 boundary leaves each
    shapes["halo_send_0"] = ((nranks, 8), i32)
    shapes["halo_send_1"] = ((nranks, 8), i32)
    sds = {k: jax.ShapeDtypeStruct(s, d) for k, (s, d) in shapes.items()}
    meta = dict(depth=depth, rounds=2, k3=k3)
    return sds, meta


def lower_bltc(nranks: int, n_per_rank: int, multi_pod: bool):
    cfg = TreecodeConfig(theta=0.8, degree=8, leaf_size=4000,
                         batch_size=4000)
    # scale leaf to keep the dry-run shapes faithful to the paper's
    # N_L = 4000 while bounding compile-time constants
    sds, meta = synthetic_shapes(nranks, n_per_rank, cfg)
    kernel = cfg.make_kernel()
    degree = cfg.degree
    axis = "data"
    if multi_pod:
        mesh = auto_mesh((2, nranks // 2), ("pod", "data"))
        spec = P(("pod", "data"))
        axes = ("pod", "data")
    else:
        mesh = auto_mesh((nranks,), ("data",))
        spec = P("data")
        axes = ("data",)

    perm_rounds = (
        (1, tuple((s, s + 1) for s in range(nranks - 1))),
        (-1, tuple((s, s - 1) for s in range(1, nranks))),
    )

    def spmd(args, q):
        a = {k: v[0] for k, v in args.items()}
        q_sorted = q[0][a["charges_perm"]]
        lo, hi = a["node_lo"], a["node_hi"]
        qhat = jnp.zeros((lo.shape[0], meta["k3"]), q_sorted.dtype)
        for lvl in range(meta["depth"]):
            gidx = a[f"bucket_gather_{lvl}"]
            nodes = a[f"bucket_nodes_{lvl}"]
            center = 0.5 * (lo[nodes] + hi[nodes])
            pts, qb = ceval._gathered(a["src_sorted"], q_sorted, gidx,
                                      fill=center)
            qh = ops.modified_charges(pts, qb, lo[nodes], hi[nodes],
                                      degree=degree, backend="xla")
            qhat = qhat.at[nodes].add(qh)
        grids = cheby.cluster_grid(lo, hi, degree)
        tgt = a["tgt_batched"]
        phi = ops.batch_cluster_eval(a["approx_idx"], tgt, grids, qhat,
                                     kernel=kernel, backend="xla",
                                     r2_mode="matmul")
        leaf_pts, leaf_q = ceval._gathered(a["src_sorted"], q_sorted,
                                           a["leaf_gather"])
        phi += ops.batch_cluster_eval(a["direct_idx"], tgt, leaf_pts,
                                      leaf_q, kernel=kernel, backend="xla")
        g_lo = jax.lax.all_gather(lo, axes)
        g_hi = jax.lax.all_gather(hi, axes)
        g_qhat = jax.lax.all_gather(qhat, axes)
        g_grids = cheby.cluster_grid(g_lo.reshape(-1, 3),
                                     g_hi.reshape(-1, 3), degree)
        phi += ops.batch_cluster_eval(a["remote_approx_idx"], tgt, g_grids,
                                      g_qhat.reshape(-1, meta["k3"]),
                                      kernel=kernel, backend="xla",
                                      r2_mode="matmul")
        recv_p, recv_q = [], []
        for i, (off, pairs) in enumerate(perm_rounds):
            send_idx = a[f"halo_send_{i}"]
            safe = jnp.maximum(send_idx, 0)
            valid = (send_idx >= 0)[:, None]
            sp = jnp.where(valid[..., None], leaf_pts[safe], 0.0)
            sq = jnp.where(valid, leaf_q[safe], 0.0)
            recv_p.append(jax.lax.ppermute(sp, axes, pairs))
            recv_q.append(jax.lax.ppermute(sq, axes, pairs))
        phi += ops.batch_cluster_eval(
            a["remote_direct_idx"], tgt,
            jnp.concatenate(recv_p, 0), jnp.concatenate(recv_q, 0),
            kernel=kernel, backend="xla")
        return phi.reshape(-1)[a["gather_index"]][None]

    specs = {k: spec for k in sds}
    fn = jax.jit(jax.shard_map(
        spmd, mesh=mesh, in_specs=(specs, spec), out_specs=spec,
        check_vma=False))
    q_sds = jax.ShapeDtypeStruct((nranks, n_per_rank), jnp.float32)
    t0 = time.time()
    lowered = fn.lower(sds, q_sds)
    compiled = lowered.compile()
    compile_s = time.time() - t0
    mem = compiled.memory_analysis()
    totals = analyze(compiled.as_text())
    per_rank_interactions = (
        sds["approx_idx"].shape[1] * sds["approx_idx"].shape[2]
        * cfg.resolved_batch_size() * meta["k3"]
        + sds["direct_idx"].shape[1] * sds["direct_idx"].shape[2]
        * cfg.resolved_batch_size() * cfg.leaf_size)
    return {
        "mesh": "x".join(str(s) for s in mesh.devices.shape),
        "status": "ok",
        "compile_s": round(compile_s, 1),
        "per_device": {
            "argument_bytes": mem.argument_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
        },
        "flops_per_device": totals.flops,
        "bytes_per_device": totals.hbm_bytes,
        "collectives": totals.collectives,
        "roofline": {
            "compute_s": totals.flops / PEAK_FLOPS,
            "memory_s": totals.hbm_bytes / HBM_BW,
            "collective_s": totals.collective_bytes / ICI_BW,
        },
        "model_interactions_per_rank": per_rank_interactions,
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--multi", action="store_true")
    ap.add_argument("--n-per-rank", type=int, default=262144)
    ap.add_argument("--out", default="")
    args = ap.parse_args()
    nranks = 512 if args.multi else 256
    res = lower_bltc(nranks, args.n_per_rank, args.multi)
    js = json.dumps(res, indent=1, default=float)
    print(js)
    if args.out:
        with open(args.out, "w") as f:
            f.write(js)


if __name__ == "__main__":
    main()
