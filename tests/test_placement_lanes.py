"""The continuous engine's lanes: ``placement.plan_disaggregation`` puts
prefill on the fastest lane and decode where a step costs least, a step
on the prefill lane priced as one decode plus one prefill."""
import pytest

from repro.serve.placement import GroupLoad, plan_disaggregation


def _lanes(*dead):
    return [GroupLoad(n, None, alive=n not in dead)
            for n in ("accel", "host")]


# (prefill times, decode times) -> (prefill lane, decode lane)
CASES = {
    # a TPU beside its host: 2.5 ms decode + 5.4 ms prefill beats 0.1 s
    "colocates": ({"accel": 5.4e-3, "host": 2.0},
                  {"accel": 2.5e-3, "host": 0.1}, "accel", "accel"),
    # the other lane's decode beats the prefill lane's sum
    "separates": ({"accel": 5.4e-3, "host": 2.0},
                  {"accel": 2.5e-3, "host": 6e-3}, "accel", "host"),
    # equal lanes: sharing would add the prefill to every step
    "equal_lanes_separate": ({"accel": 1.0, "host": 1.0},
                             {"accel": 1.0, "host": 1.0}, "accel", "host"),
    # the host prefills fastest, and its sum beats the other decode
    "prefill_on_host": ({"accel": 3.0, "host": 1.0},
                        {"accel": 2.0, "host": 0.1}, "host", "host"),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_decode_lane_by_cost(name):
    pre, dec, want_pre, want_dec = CASES[name]
    plan = plan_disaggregation(_lanes(), pre, dec)
    assert (plan.prefill_group, plan.decode_group) == (want_pre, want_dec)
    assert plan.disaggregated is (want_pre != want_dec)
    assert plan.est_prefill_s == pre[want_pre]
    assert plan.est_decode_s == dec[want_dec]
    # why the lane won: the shared lane's step beside every decode
    shared = plan.alternatives[f"decode+prefill:{want_pre}"]
    assert shared == pytest.approx(pre[want_pre] + dec[want_pre])
    assert set(plan.alternatives) == {
        "prefill:accel", "prefill:host", "decode:accel", "decode:host",
        f"decode+prefill:{want_pre}"}


@pytest.mark.parametrize("dead", ["accel", "host"])
def test_one_alive_lane_takes_both_phases(dead):
    """A dead lane is never chosen, however cheap its estimates: the
    one alive lane takes prefill and decode."""
    cheap = {"accel": 1e-6, "host": 1e-6}
    dear = {"accel": 5.0, "host": 5.0}
    alive = "host" if dead == "accel" else "accel"
    pre = {dead: cheap[dead], alive: dear[alive]}
    dec = {dead: cheap[dead], alive: dear[alive]}
    plan = plan_disaggregation(_lanes(dead), pre, dec)
    assert (plan.prefill_group, plan.decode_group) == (alive, alive)
    assert not plan.disaggregated
    assert not any(k.endswith(":" + dead) for k in plan.alternatives)


def test_no_alive_lane_gives_no_plan():
    assert plan_disaggregation(_lanes("accel", "host"),
                               {"accel": 1.0, "host": 1.0},
                               {"accel": 1.0, "host": 1.0}) is None


def test_lane_without_estimate_is_never_cheapest():
    """A lane missing from the estimates is priced infinite."""
    plan = plan_disaggregation(_lanes(), {"accel": 1.0},
                               {"accel": 1.0})
    assert (plan.prefill_group, plan.decode_group) == ("accel", "accel")
