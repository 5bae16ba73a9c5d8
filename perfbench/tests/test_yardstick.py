"""The yardstick's arithmetic: FLOP and byte functions against hand-worked
values, the generator as a pure function of the seed, the trace reduction
on a small recorded trace, the manifest against the files it names."""

import json
import os

import numpy as np
import pytest

from perfbench import flops, harness, trace, traffic
from perfbench.models import mistral, vgg

HERE = os.path.dirname(os.path.abspath(__file__))
MISTRAL = harness.load("configs", "mistral-7b-v0.1-l2")
VGG = harness.load("configs", "vgg16")


def test_mistral_parameters_by_hand():
    # q 4096x4096, k and v 4096x1024, out 4096x4096; gate, up, down 4096x14336; two norms
    by_hand = 2 * 4096 * 4096 + 2 * 4096 * 1024 + 3 * 4096 * 14336 + 2 * 4096
    assert mistral.layer_params(MISTRAL) == by_hand == 218_112_000
    assert mistral.params(MISTRAL) == 2 * by_hand + 2 * 32000 * 4096 + 4096
    assert mistral.params(dict(MISTRAL, num_hidden_layers=32)) == 7_241_732_096  # the published 7.24 B


def test_mistral_train_flops_by_hand():
    # s8192 under a 4096 window: positions 0..4095 see (i + 1) keys, the rest 4096
    keys = (4096 * 4097 / 2 + 4096 * 4096) / 8192
    assert flops.mean_keys(8192, 4096) == pytest.approx(keys) == pytest.approx(3072.25)
    assert flops.mean_keys(2048, 4096) == pytest.approx(1024.5)
    matmul = 2 * (218_112_000 - 8192) + 32000 * 4096
    per_token = 6 * matmul + 3 * 4 * 4096 * keys * 2
    assert mistral.train_flops_per_token(MISTRAL, 8192) == pytest.approx(per_token)
    assert per_token == pytest.approx(3.7057e9, rel=1e-3)        # "3.7 GFLOP a token"
    assert per_token * 16384 == pytest.approx(60.7e12, rel=2e-3)  # a step


def test_vgg16_by_hand():
    assert vgg.params(VGG) == 138_357_544
    assert vgg.macs_per_image(VGG) == 15_470_264_320  # 15.5 GMAC forward
    assert vgg.train_flops_per_image(VGG) == 6 * 15_470_264_320


def test_flash_bytes():
    # b1 s128: q and o at 32 heads, k and v at 8, 128 wide, bf16
    assert flops.flash_bytes_fwd(MISTRAL, 1, 128) == 128 * 128 * (64 + 16) * 2


def test_train_batches_are_a_pure_function_of_the_seed():
    mix = {"kind": "tokens", "batch": 2, "seq": 16, "pool": 3}
    cfg = {"vocab_size": 50}
    a = traffic.train_batches(mix, cfg, 2 ** 31 + 9)
    b = traffic.train_batches(mix, cfg, 2 ** 31 + 9)
    c = traffic.train_batches(mix, cfg, 2 ** 31 + 9, rank=1)
    assert all(np.array_equal(x[0], y[0]) for x, y in zip(a, b))
    assert not np.array_equal(a[0][0], c[0][0])
    assert np.array_equal(np.asarray(a[0][1])[:, :-1], np.asarray(a[0][0])[:, 1:])
    assert len({bytes(np.asarray(r)) for x in a for r in x[0]}) == 6  # all rows differ


def test_union_and_gaps():
    assert trace.union_seconds([(0, 1), (0.5, 1), (3, 1)]) == pytest.approx(2.5)
    t = trace.Trace(ops={"d": [("a", 0.0, 1.0), ("b", 0.5, 1.0), ("a", 3.0, 1.0)]},
                    host=[("window", 0.0, 5.0), ("waiting", 1.4, 1.7)])
    assert trace.busy_seconds(t, 0.0, 5.0) == pytest.approx(2.5)
    assert trace.busy_seconds(t, 0.75, 3.5) == pytest.approx(1.25)
    assert trace.seconds_by_name(trace.all_ops(t, 0, 5), "^a$") == pytest.approx(2.0)
    assert trace.top_ops(t, 0, 5)[0] == ["a", pytest.approx(2.0)]
    gaps = dict(trace.idle_gaps(t, 0.0, 5.0))
    assert gaps == {"waiting": pytest.approx(1.5), "window": pytest.approx(1.0)}
    assert trace.window_of(t) == (0.0, 5.0)


def test_reduction_of_a_recorded_trace():
    """Three jitted calls recorded on the CPU backend, each inside the
    benchmark's in_step_program annotation, 10 ms of sleep between them."""
    t = trace.load(os.path.join(HERE, "data", "cpu_3steps.xplane.pb"),
                   device_plane=r"^/host:CPU$", ops_line=r"^tf_XLAPjRtCpuClient",
                   modules_line=r"^$")
    lo, hi = trace.window_of(t)
    assert sum(1 for n, _, _ in t.host if n == "in_step_program") == 3
    busy = trace.busy_seconds(t, lo, hi)
    assert 0 < busy < hi - lo
    assert (hi - lo) > 0.03                       # three sleeps of 10 ms
    idle = 1 - busy / (hi - lo)
    assert 0.5 < idle < 1.0
    dots = trace.seconds_by_name(trace.all_ops(t, lo, hi), r"^dot_general")
    assert 0 < dots <= busy
    assert sum(1 for n, _, _ in trace.all_ops(t, lo, hi) if n.startswith("dot_general")) == 3
    gaps = dict(trace.idle_gaps(t, lo, hi))
    assert gaps["between_dispatch"] > 0.02


def test_manifest_names_files_that_exist():
    m = harness.manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    for c in m["configs"]:
        cfg = json.load(open(os.path.join(harness.ROOT, c["file"])))
        assert cfg["source"] == c["source"] and cfg["reduced"] == c["reduced"]
    for w in m["workloads"]:
        cell = harness.cell(w["name"])
        assert cell["why"] == w["why"] and len(w["why"]) <= 200
        reported = harness.metric_names(w["name"], traced=False)
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.metric_names(w["name"], traced=True)
    for x in m["per_layer"]:
        spec = harness.load("metrics", x["name"])
        # the manifest's list of cells is the only one: a metric's file has none to go stale
        assert "workloads" not in spec and x["workloads"], x["name"]
        for key in ("layer", "unit", "better", "source", "moves"):
            assert spec[key] == x[key], (x["name"], key)
        assert os.path.exists(os.path.join(harness.BENCH, "readers", spec["reader"] + ".py"))
        for w in x["workloads"]:  # every cell that reports it reports what it moves
            assert x["moves"] in harness.metric_names(w, traced=False)
    on_disk = {f[:-len(".json")] for f in os.listdir(os.path.join(harness.BENCH, "metrics"))}
    assert on_disk == {x["name"] for x in m["per_layer"]}  # no metric file lacks an entry
    used = {harness.load("metrics", x["name"])["reader"] for x in m["per_layer"]}
    helpers = {"__init__", "program_spans", "kernel_roofline"}  # shared by readers, no metric's own
    readers = {f[:-len(".py")] for f in os.listdir(os.path.join(harness.BENCH, "readers"))
               if f.endswith(".py")}
    assert readers - helpers == used
