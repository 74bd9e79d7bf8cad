"""The reduction from trace events to device metrics, on a made-up trace."""
import pytest

from bench import trace_reduce as tr

E = tr.Event

# Operations as the TPU's trace names them: the HLO text of the
# instruction. A Mosaic kernel, a fusion that gathers from a kernel's
# output, and a loop whose body runs a kernel.
MOSAIC = ('%bltc_direct.3 = f32[320,1,2048]{2,1,0:T(1,128)S(1)} '
          'custom-call(f32[2048,3]{1,0:T(8,128)} %p.1, s32[320,4]{1,0} '
          '%p.2), custom_call_target="tpu_custom_call"')
GATHER = ('%fusion.16 = f32[2097152]{0:T(1024)S(1)} fusion(f32[1000000]'
          '{0:T(1024)} %custom-call.56, s32[2097152]{0:T(1024)} %p.3), '
          'kind=kLoop, calls=%fused_computation.16')
LOOP = ('%while.20 = (s32[]{:T(128)}, f32[4,320,2048]{2,1,0:T(8,128)S(1)}) '
        'while((s32[]{:T(128)}, f32[4,320,2048]{2,1,0:T(8,128)S(1)}) '
        '%tuple.4), condition=%cond.1, body=%body.2')


def _mosaic(name):
    return (f'%{name} = f32[8]{{0}} custom-call(f32[8]{{0}} %p.1), '
            'custom_call_target="tpu_custom_call"')


def _trace():
    dev0 = [E(_mosaic("custom-call.1"), 0.0, 1.0),
            E("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %custom-call.1)",
              0.5, 2.0),
            E("%all-reduce.3 = f32[8]{0} all-reduce(f32[8]{0} %p)",
              3.0, 4.0),
            E("%fusion.2 = f32[8]{0} fusion(f32[8]{0} %custom-call.1)",
              9.0, 11.0)]          # mostly after the window
    dev1 = [E(_mosaic("modified_charges.7"), 1.0, 1.5),
            E("%collective-permute-done.1 = f32[8]{0} "
              "collective-permute-done(f32[8]{0} %p)", 2.0, 2.5)]
    host = [E("bench.window", 0.0, 5.0), E("bench.execute", 2.2, 2.8),
            E("bench.replan", 4.1, 4.9)]
    return {0: dev0, 1: dev1}, host


def test_busy_union_and_idle_share():
    devs, host = _trace()
    s = tr.reduce_trace(devs, host, tr.window_of(host))
    # device 0 busy [0, 2] + [3, 4] = 3 s; device 1 busy 1 s; mean 2 s
    assert s.window_s == 5.0 and s.devices == 2
    assert s.busy_s == pytest.approx(2.0)
    assert s.idle_share == pytest.approx(0.6)


def test_kernel_sums():
    devs, host = _trace()
    s = tr.reduce_trace(devs, host, (0.0, 5.0))
    assert s.kernel_s == pytest.approx((1.0 + 0.5) / 2)
    ops = dict(s.device_ops)
    assert ops["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %custom-call.1)"] \
        == pytest.approx(1.5 / 2)


@pytest.mark.parametrize("name,meta,pallas", [
    (MOSAIC, "", True),
    (GATHER, "", False),
    (LOOP, "", False),
    # the short name, with the instruction's text in the metadata
    ("bltc_direct.3", MOSAIC, True),
    ("fusion.16", GATHER, False),
    # a custom call that is not a Mosaic kernel
    ('%custom-call.9 = f32[8]{0} custom-call(f32[8]{0} %p), '
     'custom_call_target="Sharding"', "", False),
], ids=["mosaic", "gather-of-a-kernel", "loop", "mosaic-meta",
        "gather-meta", "other-custom-call"])
def test_is_pallas_reads_the_operations_own_instruction(name, meta, pallas):
    assert tr.is_pallas(E(name, 0.0, 1.0, meta)) is pallas


def test_op_totals_keep_every_operation():
    # Thirty operations, the kernel the cheapest of them in each of its
    # three calls: the ten costliest leave it out, the totals do not, and
    # only the kernel's own time is Pallas time.
    evs, t = [], 0.0
    for i in range(30):
        evs.append(E(f"%fusion.{i} = f32[8]{{0}} fusion(f32[8]{{0}} "
                     "%custom-call.1)", t, t + 0.1 + 0.01 * i))
        t += 0.5
    for k in range(3):
        evs.append(E(MOSAIC, t, t + 0.05))
        t += 0.1
    s = tr.reduce_trace({0: evs}, [], (0.0, t))
    assert len(s.device_ops) == 10 and len(s.op_totals) == 31
    assert MOSAIC not in dict(s.device_ops)
    assert s.op_totals[MOSAIC] == pytest.approx(0.15)
    assert s.kernel_s == pytest.approx(0.15)
    assert sum(s.op_totals.values()) == pytest.approx(s.busy_s)


def test_idle_gaps_are_named_by_the_host_span():
    devs, host = _trace()
    s = tr.reduce_trace(devs, host, (0.0, 5.0))
    gaps = s.idle_gaps
    assert gaps[0] == ("bench.window", pytest.approx(2.5))    # dev1 2.5-5
    assert ("bench.execute", pytest.approx(1.0)) in gaps       # dev0 2-3
    assert ("bench.replan", pytest.approx(1.0)) in gaps       # dev0 4-5


def test_no_device_ops_reads_nothing():
    assert tr.reduce_trace({0: []}, [], (0.0, 1.0)) is None


def test_merging_nested_and_touching_intervals():
    evs = [E("a", 0, 3), E("b", 1, 2), E("c", 3, 4), E("d", 6, 7)]
    assert tr.busy_intervals(evs, 0, 10) == [(0, 4), (6, 7)]
    assert tr.idle_gaps([(0, 4), (6, 7)], 0, 10) == [(4, 6), (7, 10)]
