"""A run with its timed path broken underneath comes out not correct:
one case for each fault the cells can have."""
from bench_tiny import run_tiny


def _after_setup(breaks):
    """A traffic hook that breaks the traffic's system once it is set up."""
    def hook(traffic):
        setup = traffic.setup

        def broken_setup():
            setup()
            breaks(traffic)
        traffic.setup = broken_setup
    return hook


def _scale_potentials(traffic):
    # every potential off by 3e-5, where the evaluation produces it
    plan = traffic.plan_
    execute = plan.execute
    plan.execute = lambda q, **kw: execute(q, **kw) * (1.0 + 3e-5)
    replan = plan.replan

    def broken_replan(points, **kw):
        new = replan(points, **kw)
        run = new.execute
        new.execute = lambda q, **kw2: run(q, **kw2) * (1.0 + 3e-5)
        return new
    plan.replan = broken_replan


def test_eval_answer_altered():
    line = run_tiny("coulomb_1m.eval", backend="xla",
                    traffic_hook=_after_setup(_scale_potentials))
    assert line["correct"] is False and line["failed"] == line["attempted"]


def test_solve_answer_altered():
    line = run_tiny("coulomb_1m.solve", backend="xla",
                    traffic_hook=_after_setup(_scale_potentials))
    assert line["correct"] is False and line["failed"] == line["attempted"]


def _stale_replan(traffic):
    # a replan that returns its plan unchanged: every solve of the window
    # evaluates the geometry of the last set-up
    plan = traffic.plan_
    plan.replan = lambda points, **kw: plan


def test_solve_replan_returns_its_plan_unchanged():
    line = run_tiny("coulomb_1m.solve", backend="xla",
                    traffic_hook=_after_setup(_stale_replan))
    assert line["correct"] is False and line["failed"] >= 1
