"""Self-check of the yardstick, runnable on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_selfcheck.py -q

The trace reduction on a small trace recorded on the chip in this PR, the
operation and byte functions against hand-worked values for both
configurations, the generators (identical for one seed, another order for
two), and a lint of BENCHMARK.json against the contract's rules.
"""

import json
import os
import re
import sys

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from harness import manifest, peaks, roofline, stats, trace, traffic  # noqa: E402

MANIFEST = os.path.join(ROOT, "BENCHMARK.json")


def _load(rel):
    return manifest.load_module(os.path.join(BENCH, rel))


# ------------------------------------------------------------------ trace

def test_interval_arithmetic():
    assert trace.union([(5, 9), (0, 3), (2, 4)]) == [[0, 4], [5, 9]]
    assert trace.total([[0, 4], [5, 9]]) == 8
    # [0,10) minus [2,4) and [8,12) leaves 2 + 4
    assert trace.subtract([[0, 10]], [[2, 4], [8, 12]]) == 6
    assert trace.base_name("fusion.123") == "fusion"
    assert trace.base_name("mxtpu_flash_dense_fwd") == "mxtpu_flash_dense_fwd"
    assert trace.base_name("all-reduce-start.3") == "all-reduce-start"


def test_synthetic_trace_exposed_collective_and_gaps():
    raw = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["fusion.1", 0, 100], ["all-reduce.2", 50, 100],
            ["mxtpu_flash_dense_fwd", 200_000, 50_000]]},
            {"name": "XLA Modules", "events": [["jit_step(1)", 0, 250_000]]}]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["bench.train_step", 0, 300_000], ["other", 0, 10]]}]}]}
    red = trace.Reduced(raw, window_s=1.0)
    assert red.n_devices == 1
    assert red.busy_s() == pytest.approx((150 + 50_000) / 1e9)
    tot, exposed = red.collective_seconds()
    assert tot == pytest.approx(100e-9) and exposed == pytest.approx(50e-9)
    assert red.kernel_seconds("mxtpu_flash_dense_fwd") == \
        (pytest.approx(50_000e-9), 1)
    assert red.module_seconds("jit_step") == (pytest.approx(250_000e-9), 1)
    gaps = dict(red.idle_gaps())
    assert gaps["bench.train_step"] == pytest.approx((200_000 - 150) / 1e9)
    assert red.top_ops(1)[0][0] == "mxtpu_flash_dense_fwd"


@pytest.mark.parametrize("name,kernel,collective", [
    ("train_1chip.json.gz", "mxtpu_flash_dense_fwd", False),
    ("train_4chip.json.gz", "mxtpu_flash_dense_bwd", True),
    ("serve_1chip.json.gz", "mxtpu_ragged_decode", False),
])
def test_recorded_trace(name, kernel, collective):
    """Small traces recorded on the v5e by this PR's own runs."""
    path = os.path.join(BENCH, "fixtures", name)
    if not os.path.exists(path):
        pytest.skip(f"{name} was not recorded")
    red = trace.Reduced(trace.load_fixture(path), window_s=1.0)
    assert red.n_devices == (4 if collective else 1)
    busy = red.busy_s()
    assert busy > 0
    secs, calls = red.kernel_seconds(kernel)
    assert calls > 0 and 0 < secs < busy
    ops = red.op_seconds()
    assert abs(sum(ops.values()) - busy) / busy < 0.5   # little overlap
    tot, exposed = red.collective_seconds()
    if collective:
        assert tot > 0 and 0 <= exposed <= tot
    else:
        assert tot == 0
    assert any(n.startswith("bench.") for n, _, _ in red.host)
    assert red.idle_gaps()


# ------------------------------------------------------------ flops, bytes

def test_bert_large_hand_worked():
    ref = _load("configs/bert_reference.py")
    cfg = manifest.load_json(os.path.join(BENCH, "configs/bert-large.json"))
    job = manifest.load_json(os.path.join(BENCH, "traffic/mlm-b32-t512.json"))
    # encoder 29,686,813,949,952 + attention 2,473,901,162,496 + heads
    # 471,568,613,376 (worked by hand from 6x the matmul sizes)
    assert ref.train_flops_per_step(cfg, job, 32) == 32_632_283_725_824
    shp = ref.attention_shape(cfg, job, 32)
    assert shp == {"B": 32, "H": 16, "T": 512, "D": 64, "causal": False}
    flops, nbytes = roofline.attention_fwd(**shp)
    assert (flops, nbytes) == (34_359_738_368, 134_217_728)
    flops_b, nbytes_b = roofline.attention_bwd(**shp)
    assert (flops_b, nbytes_b) == (2 * flops, 2 * nbytes)
    peak = peaks.peak("TPU v5 lite")
    # operations bound it: 34.36e9 / 197e12 = 174.4 us > 134.2e6 / 819e9
    assert roofline.seconds(flops, nbytes, peak) == \
        pytest.approx(34_359_738_368 / 197e12)


def test_gpt2_small_hand_worked():
    ref = _load("configs/gpt2_reference.py")
    cfg = manifest.load_json(os.path.join(BENCH, "configs/gpt2-small.json"))
    job = manifest.load_json(os.path.join(BENCH, "traffic/lm-b16-t512.json"))
    # blocks 16,698,832,846,848 + causal attention 927,712,935,936 + head
    # 7,588,552,900,608 for 64 rows of 512
    assert ref.train_flops_per_step(cfg, job, 64) == 25_215_098_683_392
    shp = ref.attention_shape(cfg, job, 16)
    assert roofline.attention_bwd(**shp) == (12_884_901_888.0, 100_663_296)
    assert ref.kv_bytes_per_token(cfg) == 36_864
    # one token decoded against 350 cached: blocks 2*12*(4*768^2+2*768*3072)
    # = 169,869,312; attention 4*12*768*350 = 12,902,400; head 2*768*50257
    assert ref.forward_flops(cfg, 1, 350, 1) == \
        169_869_312 + 12_902_400 + 77_194_752
    # decode kernel, one layer: 100 sequences, 35,000 live positions, page 16
    flops, nbytes = roofline.paged_decode(35_000, 100, 12, 64, 16)
    assert flops == 4 * 35_000 * 768
    assert nbytes == 2 * (35_000 + 800) * 768 * 2
    assert roofline.seconds(flops, nbytes, peaks.peak("TPU v5 lite")) == \
        pytest.approx(nbytes / 819e9)
    # a 128-token chunk after 512 cached positions
    flops, nbytes = roofline.paged_prefill(128, 512, 12, 64)
    assert flops == 4 * (128 * 512 + 128 * 129 / 2) * 768
    assert nbytes == (2 * 640 + 2 * 128) * 768 * 2


def test_unknown_device_is_an_error(monkeypatch):
    monkeypatch.setenv("MXTPU_PEAK_FLOPS", "1e15")      # no override exists
    assert peaks.peak("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(SystemExit):
        peaks.peak("cpu")


def test_percentile():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(list(range(101)), 95) == 95
    assert stats.percentile([], 95) is None


# -------------------------------------------------------------- generators

@pytest.mark.parametrize("mix_name", ["chat-poisson", "sessions-zipf"])
def test_serving_generator(mix_name):
    mix = manifest.load_json(os.path.join(BENCH, "traffic", mix_name + ".json"))

    def gen(seed):
        return traffic.serve_requests(mix, 12.0, 10.0, seed, 50257, 5.0)[0]

    def key(r):
        return (r["segment"], len(r["prompt_ids"]), r["max_new_tokens"])

    a, b, c = gen(5), gen(5), gen(3_000_000_019)
    assert len(a) == len(b) == len(c)
    for x, y in zip(a, b):
        assert x["due"] == y["due"] and key(x) == key(y)
        assert np.array_equal(x["prompt_ids"], y["prompt_ids"])
    # another seed: the same schedule of sizes and arrivals, other tokens
    assert [key(x) for x in a] == [key(x) for x in c]
    assert [x["due"] for x in a] == [x["due"] for x in c]
    own = -mix["output"]["min"]         # the request's own tokens differ
    assert not all(np.array_equal(x["prompt_ids"][own:], y["prompt_ids"][own:])
                   for x, y in zip(a, c))
    win = [r for r in a if r["in_window"]]
    assert len(win) == 120
    pre = mix["preroll_s"]
    assert all(pre <= r["due"] < pre + 10.0 for r in win)
    assert all(len(r["prompt_ids"]) + r["max_new_tokens"] <= mix["max_total"]
               for r in a)
    if mix.get("shared_prefix"):
        heads = {r["prompt_ids"][:512].tobytes() for r in a}
        assert 1 < len(heads) <= mix["shared_prefix"]["personas"]
    else:
        lens = [len(r["prompt_ids"]) for r in a]
        assert min(lens) >= 32 and max(lens) <= 768


def test_training_generator():
    ref = _load("configs/bert_reference.py")
    cfg = manifest.load_json(os.path.join(BENCH, "configs/bert-large.json"))
    job = manifest.load_json(os.path.join(BENCH, "traffic/mlm-b32-t512.json"))

    def first(seed, n=2):
        g = traffic.train_batches(ref.batch_fields(cfg, job), ref.finish_batch,
                                  32, cfg["vocab_size"], 512, seed)
        return [next(g) for _ in range(n)]

    a, b, c = first(9), first(9), first(2**31 + 11)
    assert all(np.array_equal(a[0][k], b[0][k]) for k in a[0])
    assert not np.array_equal(a[0]["input_ids"], c[0]["input_ids"])
    assert not np.array_equal(a[0]["input_ids"], a[1]["input_ids"])
    assert a[0]["input_ids"].shape == (32, 512)
    assert a[0]["masked_positions"].shape == (32, 76)
    rows = {r.tobytes() for r in a[0]["input_ids"]}
    assert len(rows) == 32                       # rows that all differ


# --------------------------------------------------------------------- lint

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def test_manifest_lint():
    m = manifest.load_json(MANIFEST)
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(MANIFEST) <= 64 * 1024
    assert 1 <= m["run_seconds"] <= 51 and isinstance(m["run_seconds"], int)
    cells = {w["name"]: w for w in m["workloads"]}
    cfgs = {c["name"]: c for c in m["configs"]}
    assert len(cells) == len(m["workloads"]) and len(cfgs) == len(m["configs"])
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and len(c["why"]) <= 200
        assert c["file"].startswith(m["paths"][0] + "/")
        cfg = manifest.load_json(os.path.join(ROOT, c["file"]))
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg
            assert not re.search(r"(_dim|_rank|_size|n_embd|n_inner|n_head)$",
                                 key), f"{key} is a width"
        assert os.path.exists(os.path.join(
            os.path.dirname(os.path.join(ROOT, c["file"])), cfg["reference"]))
        assert os.path.exists(os.path.join(BENCH, "adapters", cfg["adapter"]))
    four = 0
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in cfgs and w["chips"] in (1, 4)
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        four += w["chips"] == 4
        mix = manifest.load_json(os.path.join(BENCH, "traffic",
                                              w["traffic"] + ".json"))
        assert w["config"] in mix["check"]["limits"]
    assert four <= max(1, len(cells) // 4)
    assert {c["name"] for c in m["configs"]} == \
        {w["config"] for w in m["workloads"]}
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    names = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(names) == len(set(names))

    def cells_of(metric):
        return set(metric.get("workloads", cells))

    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert 0.01 <= x["bound"] <= 0.1
        assert x["source"] in ("host_clock", "device_trace")
        assert cells_of(x) <= set(cells)
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["source"] in SOURCES and x["better"] in ("lower", "higher")
        assert 1 <= len(x["layer"]) <= 200
        # every cell that reports it reports the end-to-end metric it moves
        assert x["moves"] in e2e and x["moves"] != "setup_s"
        assert cells_of(x) <= cells_of(e2e[x["moves"]])
        assert os.path.exists(os.path.join(BENCH, "metrics",
                                           x["name"] + ".py"))
        if x["name"].endswith("_roofline"):
            assert x["unit"] == "%"
            assert any("mfu" in re.split(r"[._]", y["name"])
                       and y["moves"] == x["moves"]
                       and cells_of(x) <= cells_of(y)
                       for y in m["per_layer"]), x["name"]
    for name in cells:
        mine = [x for x in m["end_to_end"] if name in cells_of(x)]
        assert len(mine) >= 2                   # setup_s and one other
        assert any(name in cells_of(x) for x in m["per_layer"])


@pytest.mark.parametrize("rel", ["tests/rehearsal/manifest.json",
                                 "tests/probes/manifest.json"])
def test_other_manifests_resolve(rel):
    """The rehearsal's cells (CPU, tiny configurations; it also holds the
    serving metrics' entries, which no cell of BENCHMARK.json lists yet) and
    the probes behind PERF.md's readings name files that are all there."""
    path = os.path.join(BENCH, rel)
    m = manifest.load_json(path)
    for w in m["workloads"]:
        cell = manifest.Cell(path, w["name"])
        assert cell.entry["config"] in cell.traffic["check"]["limits"]
        assert os.path.exists(os.path.join(BENCH, "adapters",
                                           cell.config["adapter"]))
        assert os.path.exists(os.path.join(BENCH, "configs",
                                           cell.config["reference"]))
        assert len(cell.end_to_end()) >= 2 and cell.per_layer()
        for x in cell.per_layer():
            assert os.path.exists(os.path.join(BENCH, "metrics",
                                               x["name"] + ".py"))


def test_no_cell_name_in_harness_code():
    m = manifest.load_json(MANIFEST)
    words = [w["name"] for w in m["workloads"]] + \
        [c["name"] for c in m["configs"]]
    for base in ("harness", "metrics", "adapters"):
        for fn in os.listdir(os.path.join(BENCH, base)):
            if fn.endswith(".py"):
                text = open(os.path.join(BENCH, base, fn)).read()
                for w in words:
                    assert w not in text, (fn, w)
    assert not any(w in open(os.path.join(BENCH, "run.py")).read()
                   for w in words)
