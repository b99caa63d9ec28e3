import os

import pytest

from benchmarks import trace_reduce as tr

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")

US = 1_000.0  # ns


def _planes():
    ops = [
        ("while.1", 0 * US, 100 * US),  # spans its body's operations
        ("fusion.a", 10 * US, 30 * US),
        ("splash_fwd", 50 * US, 40 * US),
        ("fusion.a", 200 * US, 50 * US),
    ]
    return [
        ("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", [("jit_step(1)", 0.0, 100 * US), ("jit_step(1)", 200 * US, 50 * US)])]),
        ("/host:CPU", [("python3", [("bench/data_wait", 100 * US, 90 * US), ("$loop.py:1 inner", 120 * US, 60 * US), ("$outer", 90 * US, 120 * US)])]),
    ]


def test_busy_union_idle_share_and_window():
    r = tr.reduce_trace(_planes())
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(250e-6)
    assert r["busy_s"] == pytest.approx(150e-6)  # nested events are not counted twice
    assert 1 - r["busy_s"] / r["window_s"] == pytest.approx(0.4)


def test_per_name_durations_are_self_times():
    r = tr.reduce_trace(_planes())
    assert r["op_s"]["while.1"] == pytest.approx(30e-6)  # 100 - 30 - 40
    assert r["op_s"]["fusion.a"] == pytest.approx(80e-6)
    assert r["op_count"]["fusion.a"] == 2
    assert r["module_s"]["jit_step(1)"] == pytest.approx(150e-6)
    assert r["module_count"]["jit_step(1)"] == 2
    assert tr.sum_matching(r["op_s"], "splash") == pytest.approx(40e-6)


def test_gap_is_named_by_the_benchmark_span_or_the_innermost_host_call():
    r = tr.reduce_trace(_planes())
    assert r["gap_count"] == 1
    assert r["idle_gaps"] == [["bench/data_wait", pytest.approx(100e-6)]]
    planes = _planes()
    planes[1][1][0][1].pop(0)  # no benchmark span: the shortest call covering half of the gap
    r = tr.reduce_trace(planes, host_thread=r"loop\.py")
    assert r["idle_gaps"][0][0] == "$loop.py:1 inner"
    assert set(tr.breakdown(r)) == {"device_ops", "idle_gaps"}


def test_no_device_operation_is_none_not_a_number():
    assert tr.reduce_trace([("/host:CPU", [("t", [("x", 0.0, 5.0)])])]) is None


def test_several_devices_average_busy_time():
    planes = _planes()
    planes.append(("/device:TPU:1", [("XLA Ops", [("fusion.a", 0.0, 250 * US)])]))
    r = tr.reduce_trace(planes)
    assert r["devices"] == 2
    assert r["busy_s"] == pytest.approx((150e-6 + 250e-6) / 2)


def test_exposed_collective_time():
    # a while spans its body (a fusion and a synchronous all-gather); an all-reduce runs after it
    ops = [("while.1", 0.0, 100 * US), ("fusion", 0.0, 30 * US), ("all-gather.1", 30 * US, 70 * US), ("all-reduce.2", 200 * US, 10 * US)]
    r = tr.reduce_trace([("/device:TPU:0", [("XLA Ops", ops)]), ("/device:TPU:1", [("XLA Ops", ops)])])
    total, exposed = tr.exposed_seconds(r, lambda n: n.startswith("all-"))
    assert total == pytest.approx(80e-6)
    assert exposed == pytest.approx(80e-6)  # the while is no work of its own: it hides nothing


def _recorded(name):
    """Traces recorded on the v5e by this benchmark (my chip runs, PR 23), cut
    to a fraction of a second and to the lines the reduction reads."""
    planes = tr.load_xplane(os.path.join(DATA, f"{name}.xplane.pb"))
    return planes, tr.reduce_trace(planes, host_thread=r"loop\.py:\d+ _iteration")


def test_recorded_training_trace():
    _, r = _recorded("train_v5e")
    assert r["devices"] == 1 and 0 < r["busy_s"] <= r["window_s"]
    assert 1 - r["busy_s"] / r["window_s"] < 0.01  # the train step keeps the chip busy
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"], rel=0.02)  # self times add up to the union
    assert [k for k in r["module_s"] if k.startswith("jit_step(")]
    from benchmarks.layer_metrics import _kernels

    share = _kernels.attention_seconds(r) / r["busy_s"]
    assert 0.1 < share < 0.3  # forward and fused backward splash kernels
    assert {k.split(".")[0] for k in r["op_s"] if _kernels.ATTENTION_OP.search(k)} == {"splash_mha_fwd_residuals", "splash_mha_dkv_no_residuals"}


def test_recorded_serving_trace_tells_decode_from_prefill():
    planes, r = _recorded("chat_v5e")
    from benchmarks.layer_metrics import _engine_programs

    got = _engine_programs.classify({"trace": r, "planes": planes})
    # both programs are named jit__unknown(<fingerprint>); in this cut the decode ran twice, the prefill once
    by_print = sorted(r["module_count"].values())
    assert by_print == [1.0, 2.0]
    assert len(got["decode"]) == 2 and len(got["prefill"]) == 1
    assert 0.04 < got["decode"][0] < 0.06 and 0.015 < got["prefill"][0] < 0.03
    labelled = sum(v for k, v in r["op_s"].items() if "/" in k)
    assert labelled > 0.9 * r["busy_s"]  # two programs: operations carry their program's label
    # the engine thread's own Python calls name the gaps
    assert any(name.startswith("$loop.py") for name, _ in r["idle_gaps"])
