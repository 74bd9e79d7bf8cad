"""Loop-aware HLO analyzer: exactness on known programs."""
import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.hlo_analysis import analyze, parse_hlo
from repro.launch.mesh import auto_mesh


def _compile(f, *args):
    return jax.jit(f).lower(*args).compile()


def test_scan_matmul_flops_exact():
    """grad through a scan of L matmuls: fwd L + bwd 2L dots, all counted
    with the while-loop trip multiplier."""
    L, D = 8, 256
    W = jnp.zeros((L, D, D), jnp.float32)

    def f(ws, x):
        def body(c, w):
            return c @ w, None
        y, _ = jax.lax.scan(body, x, ws)
        return y.sum()

    c = _compile(jax.value_and_grad(f, argnums=(0, 1)), W,
                 jnp.zeros((D, D)))
    t = analyze(c.as_text())
    want = 3 * L * 2 * D**3
    assert abs(t.flops - want) / want < 0.02, (t.flops, want)


def test_single_dot_flops():
    c = _compile(lambda a, b: a @ b, jnp.zeros((64, 128)),
                 jnp.zeros((128, 32)))
    t = analyze(c.as_text())
    assert t.flops >= 2 * 64 * 128 * 32
    assert t.flops < 2.2 * 64 * 128 * 32


def test_parse_finds_entry_and_computations():
    c = _compile(lambda x: jnp.tanh(x).sum(), jnp.zeros((32, 32)))
    comps, entry = parse_hlo(c.as_text())
    assert entry is not None and entry in comps
    assert len(comps) >= 1


def test_elementwise_flops_counted():
    """Pure elementwise program: flops come from the arith table."""
    c = _compile(lambda x: (x * x + x), jnp.zeros((1024,)))
    t = analyze(c.as_text())
    assert t.flops >= 2 * 1024  # mul + add


def test_collectives_counted_with_trips(tmp_path):
    """psum inside a scanned body over a 1-device mesh still appears in
    HLO as all-reduce; the analyzer multiplies by the trip count."""
    mesh = auto_mesh((1,), ("d",))

    def f(xs):
        def body(c, x):
            return c + jax.lax.psum(x, "d"), None
        out, _ = jax.lax.scan(body, jnp.zeros(xs.shape[1:]), xs)
        return out

    sm = jax.shard_map(f, mesh=mesh,
                       in_specs=jax.sharding.PartitionSpec(),
                       out_specs=jax.sharding.PartitionSpec(),
                       check_vma=False)
    c = jax.jit(sm).lower(jnp.zeros((6, 8))).compile()
    t = analyze(c.as_text())
    total = sum(v["count"] for v in t.collectives.values())
    # XLA may fold the trivial group; accept either 0 (optimized away
    # on 1 device) or a multiple of the 6 loop trips.
    assert total in (0, 6), t.collectives


def test_dus_counted_at_window_size():
    """scan stacking writes (L, D) via in-place dus: counted bytes must be
    ~L * window, not L * full-buffer (which would be quadratic in L)."""
    L, D = 64, 4096

    def f(xs):
        def body(c, x):
            return c, x * 2.0
        _, ys = jax.lax.scan(body, jnp.zeros(()), xs)
        return ys

    c = _compile(f, jnp.zeros((L, D)))
    t = analyze(c.as_text())
    full_quadratic = L * L * D * 4
    assert t.hbm_bytes < full_quadratic / 4, t.hbm_bytes


# ---------------------------------------------------------------------------
# host-transfer counting (repro.lint's HLO-level ground truth)
# ---------------------------------------------------------------------------


def test_count_transfers_clean_program():
    from repro.launch.hlo_analysis import count_transfers

    c = _compile(lambda x: jnp.tanh(x).sum(), jnp.zeros((64,)))
    counts = count_transfers(c.as_text())
    assert counts == {"copies": 0, "host_calls": 0, "send_recv": 0,
                      "total": 0}


def test_count_transfers_flags_host_callback():
    """A python callback compiles to a host custom-call — the counter
    must see it (positive control: the zero pins below mean something)."""
    from repro.launch.hlo_analysis import count_transfers

    def cb(x):
        return np.asarray(x) * 2

    def f(x):
        return jax.pure_callback(
            cb, jax.ShapeDtypeStruct((64,), jnp.float32), x)

    c = _compile(f, jnp.zeros((64,)))
    assert count_transfers(c.as_text())["host_calls"] >= 1


def test_finish_pass_zero_host_transfers(rng):
    """The single-device execute pass must compile with NO host
    round-trips: no cross-memory copies, host custom-calls or sends.
    This is the CPU-side ground truth for the d2h half of the
    repro.lint trace-safety rules (jax's transfer_guard only catches
    the h2d direction on the CPU backend)."""
    from repro.core import eval as ceval
    from repro.core.api import TreecodeConfig, TreecodeSolver
    from repro.launch.hlo_analysis import count_transfers

    pts = rng.random((256, 3)).astype(np.float32)
    solver = TreecodeSolver(TreecodeConfig(degree=3, leaf_size=32,
                                           backend="xla"))
    plan = solver.plan(pts)
    q = jnp.ones((256,), plan.dtype)
    opts = plan.config.exec_opts(plan.kernel)
    lowered = jax.jit(
        ceval._execute_impl, static_argnames=ceval._EXEC_OPTS).lower(
        plan.arrays, plan._charges(q), plan._params(None), **opts)
    counts = count_transfers(lowered.compile().as_text())
    assert counts["total"] == 0, counts
