"""The yardstick's arithmetic: percentiles, the seeded schedule, the
trace reduction on hand-built intervals, the operations functions
against hand-worked values, peaks and refusals."""

import json
import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmark.harness import compare, device, loader, schedule, stats
from benchmark.harness import xplane
from benchmark.harness.xplane import Event


def _config(name):
    return loader.read_json(os.path.join(ROOT, "benchmark", "configs",
                                         name + ".json"))


# -- percentiles ------------------------------------------------------------

def test_percentile_interpolates():
    assert stats.percentile(list(range(1, 201)), 95) == pytest.approx(190.05)


@pytest.mark.parametrize("n,q,ok", [(200, 95, True), (199, 95, False),
                                    (100, 90, True), (99, 90, False),
                                    (12, 95, False)])
def test_percentile_wants_ten_samples_beyond_it(n, q, ok):
    values = [float(i) for i in range(n)]
    if ok:
        stats.percentile(values, q)
    else:
        with pytest.raises(stats.TooFewSamples):
            stats.percentile(values, q)


def test_a_failed_request_is_beyond_every_percentile():
    values = [1.0] * 180 + [math.inf] * 20
    assert stats.percentile(values, 95) == math.inf
    assert stats.percentile(values, 50) == 1.0


def test_spread_is_the_interquartile_share_of_the_median():
    values = [10.0, 10.1, 10.2, 10.3, 10.4, 10.5]
    import statistics
    q1, _, q3 = statistics.quantiles(values, n=4)
    assert stats.spread(values) == pytest.approx((q3 - q1) / 10.25)


# -- the schedule -----------------------------------------------------------

TRAFFIC = {
    "rate_per_s": 8.0, "arrivals": {"process": "poisson"},
    "prompt_len": {"dist": "lognormal", "median": 128, "sigma": 0.8,
                   "min": 16, "max": 512, "step": 16},
    "output_len": {"dist": "lognormal", "median": 64, "sigma": 0.7,
                   "min": 4, "max": 256},
}


def test_same_seed_same_requests():
    a = schedule.make_schedule(TRAFFIC, 2 ** 31 + 7, 10.0, 1000)
    b = schedule.make_schedule(TRAFFIC, 2 ** 31 + 7, 10.0, 1000)
    assert a == b and len(a) == 80


def test_every_seed_gets_the_same_sizes_in_another_order():
    a = schedule.make_schedule(TRAFFIC, 1, 10.0, 1000)
    b = schedule.make_schedule(TRAFFIC, 2, 10.0, 1000)
    assert [len(x.prompt) for x in a] != [len(x.prompt) for x in b]
    assert sorted(len(x.prompt) for x in a) == \
        sorted(len(x.prompt) for x in b)
    assert sorted(x.max_new_tokens for x in a) == \
        sorted(x.max_new_tokens for x in b)
    assert a[-1].due_s == pytest.approx(b[-1].due_s, abs=0.2)


def test_a_fixed_order_leaves_the_seed_only_the_token_ids():
    fixed = dict(TRAFFIC, order_seed=23)
    a = schedule.make_schedule(fixed, 1, 10.0, 1000)
    b = schedule.make_schedule(fixed, 2, 10.0, 1000)
    assert [(x.due_s, len(x.prompt), x.max_new_tokens) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new_tokens) for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in b]
    assert a == schedule.make_schedule(fixed, 1, 10.0, 1000)


def test_schedule_keeps_to_its_limits_and_its_window():
    reqs = schedule.make_schedule(TRAFFIC, 3, 10.0, 1000)
    assert all(16 <= len(r.prompt) <= 512 and len(r.prompt) % 16 == 0
               for r in reqs)
    assert all(4 <= r.max_new_tokens <= 256 for r in reqs)
    assert all(0.0 < r.due_s < 10.0 for r in reqs)
    assert [r.due_s for r in reqs] == sorted(r.due_s for r in reqs)
    assert all(0 <= t < 1000 for r in reqs for t in r.prompt)


@pytest.mark.parametrize("change", [
    {"arrivals": {"process": "onoff"}},
    {"prompt_len": dict(TRAFFIC["prompt_len"], dist="uniform")},
])
def test_a_mix_the_generator_does_not_know_is_refused(change):
    with pytest.raises(ValueError, match="unknown"):
        schedule.make_schedule(dict(TRAFFIC, **change), 1, 10.0, 1000)


def test_lateness_reports_median_and_maximum():
    late = schedule.lateness([0.0, 1.0, 2.0], [0.001, 1.0, 2.005])
    assert late["median_ms"] == pytest.approx(1.0)
    assert late["max_ms"] == pytest.approx(5.0)


# -- the trace reduction, on hand-built intervals ---------------------------

def _op(kind, start, end, extra=""):
    return Event("%%%s.1 = f32[8]{0} %s(f32[8]{0} %%x)%s"
                 % (kind.replace("-", "_"), kind, extra), start, end)


def test_busy_is_the_union_and_idle_the_rest():
    ops = [_op("fusion", 0.0, 1.0), _op("fusion", 0.5, 1.5),
           _op("copy", 2.0, 3.0)]
    busy, window = xplane.busy_and_window(ops)
    assert busy == pytest.approx(2.5) and window == pytest.approx(3.0)
    assert xplane.idle_gaps(ops) == [(1.5, 2.0)]


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(xplane.TraceError):
        xplane.busy_and_window([])


def test_exposed_collective_is_what_no_compute_covers():
    ops = [_op("fusion", 0.0, 1.0), _op("all-reduce", 0.5, 2.0),
           _op("fusion", 1.5, 1.75),
           # a container spans its children and hides nothing
           _op("while", 0.0, 3.0)]
    assert xplane.exposed_collective_seconds(ops) == pytest.approx(0.75)


def test_mosaic_calls_are_told_from_other_custom_calls():
    mosaic = _op("custom-call", 0, 1,
                 ', custom_call_target="tpu_custom_call"')
    other = _op("custom-call", 0, 1, ', custom_call_target="ConcatBitcast"')
    user = Event('%copy.5 = f32[3]{0} copy(f32[3]{0} %custom-call.41)', 0, 1)
    assert xplane.is_mosaic(mosaic.name)
    assert not xplane.is_mosaic(other.name)
    assert not xplane.is_mosaic(user.name)
    assert xplane.opcode("jit_step(123)") == ""


def test_opcode_of_a_tuple_shaped_operation():
    name = ("%while.6 = (s32[]{:T(128)}, f32[768,50304]{1,0:T(8,128)}) "
            "while((s32[]{:T(128)}) %tuple.1), condition=%c, body=%b")
    assert xplane.opcode(name) == "while" and xplane.is_container(name)


def test_top_operations_leave_containers_out_and_add_up_names():
    ops = [_op("while", 0.0, 10.0), _op("fusion", 0.0, 2.0),
           _op("fusion", 3.0, 4.0), _op("copy", 5.0, 5.5)]
    top = xplane.time_by_name(ops)
    assert [round(s, 6) for _, s in top] == [3.0, 0.5]
    assert "while" not in " ".join(n for n, _ in top)


def test_gaps_go_to_what_the_host_was_doing():
    host = {"main": [Event("batcher.step", 0.0, 10.0),
                     Event("engine.step_fn", 1.0, 4.0),
                     Event("PjRtExecute", 1.2, 1.4),
                     Event("queue.submit", 6.0, 6.5)]}
    gaps = [(1.25, 1.35), (3.0, 3.5), (6.1, 6.2), (20.0, 21.0)]
    got = dict(xplane.attribute_gaps(
        gaps, host, prefer=("batcher.step", "queue.submit",
                            "engine.step_fn")))
    assert got["engine.step_fn"] == pytest.approx(0.6)
    assert got["queue.submit"] == pytest.approx(0.1)
    assert got["no host span"] == pytest.approx(1.0)
    # without the benchmark's own spans the shortest covering event names it
    plain = dict(xplane.attribute_gaps([(1.25, 1.35)], host))
    assert list(plain) == ["PjRtExecute"]


def test_summary_of_hand_built_planes():
    dev = xplane.DeviceTrace("/device:TPU:0", ops=[
        _op("fusion", 0.0, 1.0),
        _op("custom-call", 1.0, 1.5, ', custom_call_target="tpu_custom_call"'),
        _op("all-reduce", 2.0, 2.25)],
        modules=[Event("jit_step(1)", 0.0, 1.5), Event("jit_step(1)", 2.0, 2.25),
                 Event("jit_randint(2)", 1.6, 1.61)])
    s = xplane.summarize(xplane.Trace([dev], {"main": [Event("x", 1.4, 2.1)]}))
    assert s["busy_s"] == pytest.approx(1.75)
    assert s["window_s"] == pytest.approx(2.25)
    assert s["mosaic_calls"] == 1 and s["mosaic_seconds"] == pytest.approx(0.5)
    assert s["collective_exposed_s"] == pytest.approx(0.25)
    assert s["step_module"] == "jit_step(1)"
    assert s["modules"]["jit_step(1)"]["runs"] == 2
    assert s["idle_gaps"] == [["x", pytest.approx(0.5)]]


# -- operations and bytes, against hand-worked values -----------------------

def test_gpt2_small_required_operations_by_hand():
    from benchmark.families import gpt
    cfg = _config("gpt2-small")
    # a layer: 4 x 768^2 attention + 2 x 768 x 3072 MLP = 7,077,888;
    # 12 of them = 84,934,656; the head 768 x 50304 = 38,633,472
    assert gpt.matmul_params(cfg) == 123_568_128
    # attention at S=1024, causal: 12 layers x 3 (fwd + 2 bwd) x 2 matmuls
    # x 2 x 768 x 512 = 56,623,104
    assert gpt.train_flops_per_token(cfg, 1024) == \
        6 * 123_568_128 + 56_623_104


def test_bert_base_required_operations_by_hand():
    from benchmark.families import bert
    cfg = _config("bert-base")
    # layers 84,934,656 + transform 768^2 = 589,824 + decoder
    # 768 x 30522 = 23,440,896
    assert bert.matmul_params(cfg) == 108_965_376
    # full attention at S=512: 12 x 3 x 2 x 2 x 768 x 512 = 56,623,104
    assert bert.train_flops_per_token(cfg, 512) == \
        6 * 108_965_376 + 56_623_104


def test_flash_floor_by_hand():
    from benchmark.families import gpt
    cfg = _config("gpt2-small")
    peaks = device.peaks_of("TPU v5 lite")
    traffic = {"global_batch": 64, "seq_len": 1024}
    floor = gpt.flash_step_floor(cfg, traffic, peaks, chips=4)
    # 16 rows a chip: one causal matmul = 2 x 16 x 12 x 1024^2 x 64 / 2
    # = 12,884,901,888; 11 of them a layer, 12 layers
    assert floor["flops"] == 11 * 12 * 12_884_901_888
    # one bf16 [16,12,1024,64] = 25,165,824 bytes; 21 of them a layer
    assert floor["bytes"] == 21 * 12 * 25_165_824
    assert floor["calls"] == 48 and floor["bound"] == "compute"
    assert floor["seconds"] == pytest.approx(floor["flops"] / 197e12)


def test_paged_decode_bytes_by_hand():
    from benchmark.families import gpt
    cfg = _config("gpt2-small")
    traffic = {"engine": {"cache_dtype": "float32"}}
    # K and V of 1000 live tokens in 12 layers, 768 floats of 4 bytes
    assert gpt.paged_decode_bytes(cfg, traffic, 1000) == \
        2 * 12 * 1000 * 768 * 4


# -- peaks and refusals -----------------------------------------------------

def test_peaks_are_keyed_by_device_kind_with_their_source():
    peaks = device.peaks_of("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in peaks["source"]


def test_an_unknown_device_is_an_error_not_a_default():
    with pytest.raises(device.UnknownDevice):
        device.peaks_of("TPU v9 imaginary")


def test_a_share_over_its_peak_fails():
    assert device.share_pct("x_roofline", 50.0, 100.0) == 50.0
    with pytest.raises(device.ShareOverPeak):
        device.share_pct("x_roofline", 101.0, 100.0)


def test_the_self_check_refuses_a_clock_that_beats_the_roofline():
    # a peak so low that the real matmul chain "beats" it: the same
    # refusal a missing device sync would draw on the chip
    with pytest.raises(device.ShareOverPeak):
        device.matmul_self_check({"bf16_flops_per_s": 1.0}, n=64, chain=2)
    ok = device.matmul_self_check({"bf16_flops_per_s": 1e18}, n=64, chain=2)
    assert 0.0 < ok["share_pct"] <= 100.0


def test_worst_leaf_gap_is_a_gap_of_norms_against_the_larger_floor():
    want = {"a": 1.0, "b": 2.0, "c": 1e-9}
    got = {"a": 1.1, "b": 2.0, "c": 0.05}
    # median leaf is 1.0: c's gap is taken against it, not against 1e-9
    assert compare.worst_leaf_gap(got, want) == pytest.approx(0.1)
    assert compare.worst_leaf_gap(got, want, skip={"a"}) == \
        pytest.approx(0.05)
    with pytest.raises(ValueError):
        compare.worst_leaf_gap({"a": 1.0}, want)


def test_gradient_free_leaves_are_those_the_loss_ignores():
    norms = {"w": 1e-2, "v": 3e-3, "u": 5e-3, "k_bias": 1e-11}
    assert compare.gradient_free(norms) == {"k_bias"}


def test_a_check_prints_its_number_beside_its_limit():
    check = compare.at_most("loss_gap", 0.002, 0.001)
    assert not check.ok and "value=0.002 limit=0.001 FAILED" in check.line()
    assert compare.exactly("x", 3, 3).ok and not compare.exactly("x", 3, 4).ok
    assert not compare.at_most("nan", float("nan"), 1.0).ok


# -- the reduction on a small trace recorded on the chip --------------------

def test_reduction_of_a_trace_recorded_on_the_chip():
    """27 KB recorded on a v5e in PR 23: three runs of one jitted step
    that holds a matmul fusion and one Pallas (Mosaic) flash call, each
    inside the benchmark's ``batcher.step`` / ``engine.step_fn``
    annotations with 2 ms of host sleep between them."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "cellbench_tiny_v5e.xplane.pb")
    trace = xplane.read(path)
    assert [d.device for d in trace.devices] == ["/device:TPU:0"]
    s = xplane.summarize(trace, prefer=("batcher.step", "engine.step_fn"))
    assert s["step_module"].startswith("jit_step(")
    assert s["modules"][s["step_module"]]["runs"] == 3
    assert s["mosaic_calls"] == 3 and 0 < s["mosaic_seconds"] < 1e-4
    assert s["collective_s"] == 0
    # the chip ran for microseconds of a window of milliseconds
    assert 0 < s["busy_s"] < 1e-4 < s["window_s"] < 0.1
    assert any("[mosaic]" in name for name, _ in s["device_ops"])
    # the idle time between the runs falls to the benchmark's own span
    assert s["idle_gaps"][0][0] == "batcher.step"


def test_a_file_that_is_no_trace_is_an_error(tmp_path):
    bad = tmp_path / "x.xplane.pb"
    bad.write_bytes(b"not a trace")
    with pytest.raises(xplane.TraceError):
        xplane.read(str(bad))
    with pytest.raises(xplane.TraceError):
        xplane.find_xplane(str(tmp_path / "nowhere"))
