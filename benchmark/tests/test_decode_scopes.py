"""Self-check of the decode program's device time by part
(``metrics/_decode_scopes.py`` and its readers), on the CPU:

    JAX_PLATFORMS=cpu python3 -m pytest benchmark/tests/test_decode_scopes.py -q

First on small hand-made data with known answers, then on ``fixtures/
serve_1chip_scoped.json.gz``: a trace of ``gpt2xl-sessions`` kept on the v5e
with ``--keep-trace``, with the rows of that run's decode scope table that
the trace names merged in (``_decode_scopes.save_fixture``). A table with no
``mx.`` name (an executable cached before the scopes) and an engine with no
table at all leave every reader silent, not raising and not reading 100%
unscoped.
"""

import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "metrics"))

import _decode_scopes as D  # noqa: E402
from harness import manifest, trace  # noqa: E402

FIXTURE = os.path.join(BENCH, "fixtures", "serve_1chip_scoped.json.gz")
GPT2XL = os.path.join(BENCH, "configs", "gpt2-xl.json")
SHARES = list(D.PARTS) + [D.UNSCOPED]
READERS = SHARES + ["decode_weight_roofline"]


def reduced(ops, modules):
    raw = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [[n, s, e - s] for n, s, e in ops]},
        {"name": "XLA Modules",
         "events": [[n, s, e - s] for n, s, e in modules]}]}]}
    return trace.Reduced(raw, window_s=1.0)


# two decode programs and a chunk program between them; times in ns
MODULES = [("jit__decode_step_fn(12)", 0, 1000),
           ("jit__chunk_prefill_fn(7)", 1000, 1500),
           ("jit__decode_step_fn(12)", 2000, 3000)]
OPS = [("fusion.1", 0, 400), ("mxtpu_ragged_decode.2", 400, 500),
       ("copy-done.3", 500, 600), ("fusion.4", 600, 700),
       ("fusion.5", 700, 950), ("fusion.9", 950, 1000),
       ("fusion.1", 1000, 1400),                        # the chunk's
       ("fusion.1", 2000, 2400), ("mxtpu_ragged_decode.2", 2400, 2500),
       ("copy-done.3", 2500, 2600), ("fusion.4", 2600, 2700),
       ("fusion.5", 2700, 2950), ("fusion.9", 2950, 3000)]
TABLE = {"fusion.1": ("mx.ffn", ""), "mxtpu_ragged_decode.2":
         ("mx.attend", ""), "copy-done.3": ("mx.attn", ""),
         "fusion.4": ("mx.norm", ""), "fusion.5": ("mx.sample", "")}


class _Engine:
    def __init__(self, table):
        self.table = table

    def scope_table(self, name):
        assert name == "decode"
        if isinstance(self.table, Exception):
            raise self.table
        return dict(self.table)


class _Sut:
    def __init__(self, engine):
        self.engine = engine


def ctx_of(table, red=None, engine=True):
    ref = manifest.load_module(os.path.join(BENCH, "configs",
                                            "gpt2_reference.py"))
    return {"kind": "serve", "trace": red or reduced(OPS, MODULES),
            "sut": _Sut(_Engine(table) if engine else object()),
            "reference": ref, "config": manifest.load_json(GPT2XL),
            "device_kind": "TPU v5 lite"}


def read_all(ctx):
    out = {}
    for name in READERS:
        mod = manifest.load_module(os.path.join(BENCH, "metrics",
                                                name + ".py"))
        out[name] = mod.read(ctx)
    return out


@pytest.fixture(autouse=True)
def side_file(tmp_path, monkeypatch):
    path = tmp_path / "decode_scopes.json"
    monkeypatch.setattr(D, "SIDE_FILE", str(path))
    return path


# --------------------------------------------------------------- by hand

def test_only_the_decode_programs_operations_are_booked():
    runs, ops = D.decode_ops(reduced(OPS, MODULES))
    assert runs == [(0, 1000), (2000, 3000)]
    assert len(ops) == 12 and all(s < 1000 or s >= 2000 for _, s, _ in ops)


def test_shares_by_part_on_hand_made_data(side_file):
    out = read_all(ctx_of(TABLE))
    # 2000 ns of decode operations: ffn 800, attend 200, attn (the weight
    # copy, booked by the table) 200, norm 200, sample 500, unscoped 100
    assert out["decode_ffn_share"] == pytest.approx(40.0)
    assert out["decode_attend_share"] == pytest.approx(10.0)
    assert out["decode_attn_share"] == pytest.approx(10.0)
    assert out["decode_norm_share"] == pytest.approx(10.0)
    assert out["decode_sample_share"] == pytest.approx(25.0)
    assert out[D.UNSCOPED] == pytest.approx(5.0)       # fusion.9
    assert out["decode_ssm_state_share"] == 0.0
    assert sum(out[m] for m in SHARES) == pytest.approx(100.0)
    doc = json.loads(side_file.read_text())
    assert doc["programs"] == 2
    assert set(doc["scope_table"]) == set(TABLE)       # fusion.9 not in it
    assert doc["ops"]["fusion"]["mx.ffn"] == pytest.approx(800e-9)


def test_roofline_arithmetic_on_hand_made_data():
    out = read_all(ctx_of(TABLE))
    step = 3272147200                                  # gpt2-xl, below
    # 2 programs x step bytes at 819 GB/s over the 2000 ns of the two
    # decode programs (the chunk program between them is not counted)
    assert out["decode_weight_roofline"] == pytest.approx(
        100.0 * 2 * step / 819e9 / 2000e-9)


def test_streamed_bytes_of_gpt2_xl():
    ref = manifest.load_module(os.path.join(BENCH, "configs",
                                            "gpt2_reference.py"))
    c = manifest.load_json(GPT2XL)
    u, h, L, V = c["n_embd"], c["n_inner"], c["n_layer"], c["vocab_size"]
    layer = 2 * (3 * u * u + 3 * u + u * u + u + h * u + h + u * h + u)
    assert D.streamed_bytes(ref, c) == L * layer + 4 * V * u == 3272147200


@pytest.mark.parametrize("table", [{n: ("", "") for n in TABLE}, {}],
                         ids=["table-without-scopes", "empty-table"])
def test_a_table_with_no_scope_leaves_every_reader_silent(table, capsys,
                                                          side_file):
    """An executable that a cache kept from before the scopes: its time is
    no reading of "unscoped", and the log says why."""
    out = read_all(ctx_of(table))
    assert out == dict.fromkeys(READERS)
    assert "holds no mx. name" in capsys.readouterr().err
    assert json.loads(side_file.read_text())["why"]


@pytest.mark.parametrize("case", ["no-table-method", "table-raises",
                                  "no-trace", "train", "no-decode-run"])
def test_nothing_to_read_leaves_every_reader_silent(case):
    if case == "no-table-method":
        ctx = ctx_of(TABLE, engine=False)
    elif case == "table-raises":
        ctx = ctx_of(RuntimeError("no program 'decode' dispatched yet"))
    elif case == "no-trace":
        ctx = dict(ctx_of(TABLE), trace=None)
    elif case == "train":
        ctx = dict(ctx_of(TABLE), kind="train")
    else:
        ctx = ctx_of(TABLE, reduced(OPS, [("jit__chunk_prefill_fn(7)",
                                           0, 3000)]))
    assert read_all(ctx) == dict.fromkeys(READERS)


# ------------------------------------------------------- recorded on chip

@pytest.fixture(scope="module")
def recorded():
    raw = trace.load_fixture(FIXTURE)
    table = {k: tuple(v) for k, v in raw["scope_table"].items()}
    return raw, trace.Reduced(raw, window_s=5.0), table


def test_recorded_shares_sum_to_a_hundred(recorded):
    _, red, table = recorded
    out = read_all(ctx_of(table, red))
    assert sum(out[m] for m in SHARES) == pytest.approx(100.0, abs=0.5)
    assert out[D.UNSCOPED] < 5.0
    for m in ("decode_attn_share", "decode_ffn_share", "decode_norm_share",
              "decode_attend_share", "decode_head_share",
              "decode_sample_share", "decode_embed_share",
              "decode_kv_write_share"):
        assert out[m] > 0, m
    assert out["decode_ssm_share"] == out["decode_ssm_state_share"] == 0.0


def test_recorded_attend_share_holds_every_ragged_decode_kernel(recorded):
    _, red, table = recorded
    runs, ops = D.decode_ops(red)
    kernel = [(n, s, e) for n, s, e in ops if "mxtpu_ragged_decode" in n]
    assert len(kernel) >= 48 * len(runs) // 2 and kernel
    assert {table[n] for n, _, _ in kernel} == {("mx.attend", "")}
    total = sum(e - s for _, s, e in ops)
    attend = sum(e - s for n, s, e in ops
                 if table.get(n, ("", ""))[0] == "mx.attend")
    in_kernel = sum(e - s for _, s, e in kernel)
    out = read_all(ctx_of(table, red))
    assert in_kernel <= attend
    assert out["decode_attend_share"] == pytest.approx(
        100.0 * attend / total)
    assert out["decode_attend_share"] >= 100.0 * in_kernel / total


def test_recorded_roofline_arithmetic(recorded):
    _, red, table = recorded
    runs, ops = D.decode_ops(red)
    out = read_all(ctx_of(table, red))
    want = 100.0 * 3272147200 * len(runs) / 819e9 / (
        sum(e - s for s, e in runs) / 1e9)
    assert out["decode_weight_roofline"] == pytest.approx(want)
    assert 20.0 < out["decode_weight_roofline"] < 100.0


def test_recorded_readers_are_silent_once_the_scopes_are_stripped(recorded):
    _, red, table = recorded
    stripped = {n: ("", "") for n in table}
    assert read_all(ctx_of(stripped, red)) == dict.fromkeys(READERS)
