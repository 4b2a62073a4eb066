"""The harness end to end at toy rosters, the reference and its
control, the faults, and the yardstick's arithmetic."""

import json
import pathlib
import re
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"

TOY_CELLS = {
    # toy cell -> (the real cell it is modelled on, executor config, n)
    "toy-served.steady": ("n16-b4k.steady", "toy-served"),
    "toy-served.saturated": ("n16-b4k.saturated", "toy-served"),
    "toy-lockstep.saturated": (
        "n128-b10k-lockstep.saturated", "toy-lockstep"
    ),
}
TOY_CONFIGS = {
    "toy-served": ("n16-b4k", {"n": 4, "batch_size": 64}),
    "toy-lockstep": ("n128-b10k-lockstep", {"n": 4, "batch_size": 64}),
}


def _fake_chip(chips):
    import jax

    devices = jax.devices()
    assert len(devices) >= chips
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def add_config(root, name, like, fields, reduced=()):
    """A new configuration as a new file and a new entry."""
    cfg = json.loads((root / f"benchmarks/configs/{like}.json").read_text())
    cfg["name"] = name
    cfg["config"].update(fields)
    if "warm_shapes" in cfg:
        # the real sizes take minutes on XLA-CPU; keep the path, tiny
        cfg["warm_shapes"] = {"comb": [[8, 16]], "dual_pow": [16]}
    path = root / f"benchmarks/configs/{name}.json"
    assert not path.exists()
    path.write_text(json.dumps(cfg))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": name, "source": "toy", "reduced": list(reduced),
        "file": f"benchmarks/configs/{name}.json", "why": "a test's",
    })
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


def add_cell(root, cell, like, config, traffic_fields=None, chips=1):
    """A new cell as a new traffic file and a new entry, reporting what
    the cell it is modelled on reports."""
    traffic = json.loads(
        (root / f"benchmarks/traffic/{like}.json").read_text()
    )
    traffic.update(traffic_fields or {})
    path = root / f"benchmarks/traffic/{cell}.json"
    assert not path.exists()
    path.write_text(json.dumps(traffic))
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["workloads"].append({
        "name": cell, "config": config, "traffic": cell.split(".")[1],
        "chips": chips, "why": "a test's",
    })
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if like in metric.get("workloads", ()):
            metric["workloads"].append(cell)
    (root / "BENCHMARK.json").write_text(json.dumps(bench))


@pytest.fixture
def toy_root(tmp_path):
    """A copy of the benchmark's data with toy rosters beside the real
    ones: new files and new entries, no file that was there edited."""
    root = tmp_path / "checkout"
    (root / "benchmarks").mkdir(parents=True)
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    for sub in ("configs", "traffic", "layer_metrics"):
        shutil.copytree(ROOT / "benchmarks" / sub, root / "benchmarks" / sub)
    for name, (like, fields) in TOY_CONFIGS.items():
        add_config(root, name, like, fields)
    for cell, (like, config) in TOY_CELLS.items():
        add_cell(root, cell, like, config, {"rate_tx_per_s": 300}
                 if like.endswith("steady") else None)
    return root


@pytest.fixture
def harness(monkeypatch):
    from benchmarks import executors, run

    monkeypatch.setattr(run, "require_chip", _fake_chip)
    monkeypatch.setattr(executors, "DRAIN_LIMIT_S", 3.0)
    monkeypatch.setattr(run, "TRACE_SECONDS", 1.0)
    return run


def _metric_names(root, cell, key):
    bench = json.loads((root / "BENCHMARK.json").read_text())
    return {
        m["name"] for m in bench[key]
        if cell in m.get("workloads", [cell])
    }


@pytest.mark.parametrize("cell", sorted(TOY_CELLS))
@pytest.mark.parametrize("trace", [False, True])
def test_cell_end_to_end(harness, toy_root, cell, trace):
    result = harness.run_cell(cell, 2**31 + 11, 2.0, trace, root=toy_root)
    assert list(result)[:5] == [
        "correct", "attempted", "failed", "metrics", "device"
    ]
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert all(
        row["value"] <= row["limit"] for row in result["compared"].values()
    )
    key = "per_layer" if trace else "end_to_end"
    listed = _metric_names(toy_root, cell, key)
    assert set(result["metrics"]) <= listed
    if trace:
        assert result["device"]["window_s"] > 0
        assert "breakdown" in result
        # no device on the CPU: the roofline reader finds nothing and
        # the metric is left out, never reported as 0
        assert "modexp256_roofline" not in result["metrics"]
    else:
        assert set(result["metrics"]) == listed
        assert all(m["value"] > 0 for m in result["metrics"].values())
    json.dumps(result)  # the result line is plain JSON


def test_off_chip_exits_nonzero_with_nothing_on_stdout():
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "n16-b4k.steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode == 2
    assert proc.stdout == ""
    assert "platform 'cpu'" in proc.stderr


def test_checkout_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(
        ROOT / "benchmarks", tmp_path / "benchmarks",
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "n16-b4k.steady",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=120,
        env={"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"},
    )
    assert proc.returncode != 0
    assert proc.stdout == ""


# -- a later PR's additions are data --------------------------------------------


def test_new_config_cell_and_metric_are_new_files_only(harness, toy_root):
    """PERF.md's Open question 2 in small: a (2, 2)-mesh configuration,
    a cell on it and a per-layer metric, added as files and entries."""
    import jax

    if len(jax.devices()) < 4:
        pytest.skip("needs four (virtual) devices")
    before = {
        p: p.read_bytes()
        for p in toy_root.rglob("*")
        if p.is_file() and p.name != "BENCHMARK.json"
    }
    add_config(toy_root, "toy-mesh4", "n128-b10k-lockstep",
               {"n": 8, "batch_size": 64, "mesh_shape": [2, 2]})
    add_cell(toy_root, "toy-mesh4.saturated",
             "n128-b10k-lockstep.saturated", "toy-mesh4", chips=4)
    (toy_root / "benchmarks/layer_metrics/coin_waves_per_epoch.py").write_text(
        "def read(run):\n"
        "    stats = run.get('epoch_stats')\n"
        "    if not stats:\n"
        "        return None\n"
        "    return sum(s['coin_waves'] for s in stats) / len(stats)\n"
    )
    bench = json.loads((toy_root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "coin_waves_per_epoch", "unit": "waves/epoch",
        "better": "lower", "source": "program_span", "layer": "BBA + coin",
        "moves": "settled_tx_per_s", "workloads": ["toy-mesh4.saturated"],
    })
    (toy_root / "BENCHMARK.json").write_text(json.dumps(bench))
    result = harness.run_cell(
        "toy-mesh4.saturated", 5, 1.5, True, root=toy_root
    )
    assert result["correct"] is True, result["compared"]
    assert result["metrics"]["coin_waves_per_epoch"]["value"] >= 1
    assert result["device"]["count"] >= 4
    for path, content in before.items():
        assert path.read_bytes() == content, f"{path} was edited"


# -- correct: the faults read not correct ---------------------------------------


def _fault_cases():
    from benchmarks.faults import FAULTS

    cells = {"served": "toy-served.saturated",
             "lockstep": "toy-lockstep.saturated"}
    cases = [
        (cells[kind], kind, name)
        for kind in sorted(FAULTS) for name in sorted(FAULTS[kind])
    ]
    # the open loop drives the same served path; one fault there too
    cases.append(("toy-served.steady", "served", "half_batch"))
    return cases


@pytest.mark.parametrize("cell,kind,name", _fault_cases())
def test_fault_under_the_timed_path_reads_not_correct(
    harness, toy_root, cell, kind, name
):
    from benchmarks.faults import FAULTS

    result = harness.run_cell(
        cell, 99, 1.5, False, root=toy_root, fault=FAULTS[kind][name]
    )
    assert result["correct"] is False
    over = {
        k: v["value"] for k, v in result["compared"].items()
        if v["value"] > v["limit"]
    }
    assert over, result["compared"]


# -- the reference and its control, without the program -------------------------


def _served_obs():
    ids = ["node000", "node001", "node002"]
    txs = [bytes([i]) * 16 for i in range(12)]
    subs = [(tx, ids[i % 3], True) for i, tx in enumerate(txs)]
    batch = [
        {nid: [t for t, home, _ in subs[lo:hi] if home == nid] for nid in ids}
        for lo, hi in ((0, 6), (6, 12))
    ]
    return {
        "node_ids": ids, "submissions": subs,
        "ledgers": {nid: [dict((k, list(v)) for k, v in b.items())
                          for b in batch] for nid in ids},
        "evicted": 0, "ordered": dict.fromkeys(ids, 2),
        "settled": dict.fromkeys(ids, 2), "batch_size": 8,
    }


def _break_lost(obs):
    for ledger in obs["ledgers"].values():
        ledger[1]["node000"].pop()


def _break_duplicated(obs):
    for ledger in obs["ledgers"].values():
        ledger[1]["node000"].append(ledger[0]["node000"][0])


def _break_foreign(obs):
    for ledger in obs["ledgers"].values():
        ledger[1]["node000"].append(b"never submitted!")


def _break_misplaced(obs):
    for ledger in obs["ledgers"].values():
        ledger[1]["node001"].append(ledger[1]["node000"].pop())


def _break_forked(obs):
    tx = obs["ledgers"]["node002"][0]["node000"][0]
    obs["ledgers"]["node002"][0]["node000"][0] = tx[:-1] + b"\xff"


def _break_unsettled(obs):
    obs["settled"]["node001"] = 1


def _break_oversize(obs):
    obs["batch_size"] = 5


@pytest.mark.parametrize("number", [
    "lost", "duplicated", "foreign", "misplaced", "forked", "unsettled",
    "oversize",
])
def test_served_control_breaks_one_guarantee(number):
    """The control: the reference's own ledger with one stated
    guarantee broken has to come out as not correct, by the number
    that guarantee belongs to."""
    from benchmarks import reference

    sound = reference.compare_served(_served_obs())
    assert reference.verdict(sound), sound
    obs = _served_obs()
    globals()["_break_" + number](obs)
    broken = reference.compare_served(obs)
    assert not reference.verdict(broken)
    assert broken[number][0] > broken[number][1]


def test_served_evictions_are_counted_not_lost():
    from benchmarks import reference

    obs = _served_obs()
    _break_lost(obs)
    obs["evicted"] = 1
    assert reference.verdict(reference.compare_served(obs))


def test_lockstep_reference_predicts_the_commit_rule():
    from benchmarks import reference

    got = reference.predict_batch({
        "b": [b"2", b"1"], "a": [b"1", b"3"], "c": [b"3"],
    })
    assert got == {"a": [b"1", b"3"], "b": [b"2"]}
    assert list(got) == ["a", "b"]


def test_coin_reference_matches_the_program_and_its_key_check():
    """The reference's coin (Python ints, its own hash-to-group and
    Lagrange) tosses what the program's CommonCoin tosses."""
    from benchmarks import reference
    from cleisthenes_tpu.config import Config
    from cleisthenes_tpu.ops.backend import get_backend
    from cleisthenes_tpu.protocol.honeybadger import setup_keys

    cfg = Config(n=4)
    ids = [f"node{i:03d}" for i in range(4)]
    keys = setup_keys(cfg, ids, seed=42)
    pub = keys[ids[0]].coin_pub
    group = {"p": pub.group.p, "q": pub.group.q, "g": pub.group.g}
    shares = [(keys[i].coin_share.index, keys[i].coin_share.value)
              for i in ids[: pub.threshold]]
    ref = reference.CoinReference(group, pub.threshold, shares, pub.master)
    assert ref.key_ok
    coin = get_backend(cfg).coin(pub)
    for r in range(8):
        coin_id = b"3|node001|%d" % r
        sub = [coin.share(keys[i].coin_share, coin_id)
               for i in ids[: pub.threshold]]
        assert ref.toss(coin_id) == coin.toss(coin_id, sub)
    wrong = reference.CoinReference(
        group, pub.threshold, shares, (pub.master * 2) % group["p"]
    )
    assert not wrong.key_ok


# -- the yardstick's arithmetic --------------------------------------------------


def test_trace_reduction_on_the_recorded_fixture():
    from benchmarks import trace_reduce

    trace = json.loads((FIXTURES / "lockstep_trace_small.json").read_text())
    want = json.loads((FIXTURES / "lockstep_trace_small.want.json").read_text())
    got = trace_reduce.reduce(trace)
    assert got["devices"] == want["devices"]
    for key in ("window_s", "busy_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9)
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["device_ops"] == want["device_ops"]
    assert got["idle_gaps"] == want["idle_gaps"]
    idle = sum(s for _n, s in got["idle_gaps"])
    assert idle <= got["window_s"] - got["busy_s"] + 1e-9


def test_trace_reduction_by_hand():
    from benchmarks import trace_reduce

    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Modules", "events": [
                ["jit_f(1)", 100, 50], ["jit_g(2)", 300, 100]]},
            {"name": "XLA Ops", "events": [
                ["fusion", 100, 20], ["fusion.1", 110, 30], ["x", 300, 100],
                ["outside", 2000, 50]]},
        ]},
        {"name": "/host:CPU", "lines": [{"name": "main", "events": [
            ["traced_window", 0, 1000], ["run_epoch", 50, 500],
            ["submit", 600, 100]]}]},
    ]}
    got = trace_reduce.reduce(trace)
    assert got["window_s"] == pytest.approx(1000e-9)
    assert got["busy_s"] == pytest.approx(150e-9)  # 100-150 and 300-400
    assert got["dropped"] is False
    assert dict(got["device_ops"]) == pytest.approx(
        {"jit_g": 100e-9, "jit_f": 50e-9})
    assert dict(got["idle_gaps"]) == pytest.approx({
        "run_epoch": (50 + 150 + 150) * 1e-9,
        "submit": 100e-9,
        trace_reduce.UNNAMED: (50 + 50 + 300) * 1e-9,
    })
    with pytest.raises(ValueError):
        trace_reduce.reduce({"planes": trace["planes"][:1]})
    # a trace with no module line falls back to the op line, and says
    # when the device dropped buffers
    device = trace["planes"][0]
    device["lines"] = [device["lines"][1], {
        "name": "XLA TraceMe", "events": [["Trace Buffers Dropped", 900, 0]],
    }]
    got = trace_reduce.reduce(trace)
    assert got["busy_s"] == pytest.approx(140e-9)  # 100-140 and 300-400
    assert got["dropped"] is True and got["device_ops"] == []


def test_work_count_and_peaks():
    from benchmarks import peaks, work

    assert work.OPS_PER_EXP == 512 * 4096 == 2_097_152
    tally = {
        "modexp_12x22.comb": {"device_items": 1000, "host_items": 5},
        "modexp_12x22.dual_pow": {"device_items": 100, "host_items": 0},
        "merkle.verify_branches": {"device_items": 7, "host_items": 0},
    }
    w = work.modexp256_work(tally)
    assert w["items"] == 1100 and w["exps"] == 1200
    assert w["int8_ops"] == 1200 * 2_097_152
    v5e = peaks.peaks_for("TPU v5 lite")
    assert v5e["int8_ops_per_s"] == 393e12 and v5e["hbm_bytes_per_s"] == 819e9
    # the least time is the compute bound here; twice the time, half the share
    share = work.roofline_pct(w, 1.0, v5e)
    assert share == pytest.approx(100 * 1200 * 2_097_152 / 393e12)
    assert work.roofline_pct(w, 2.0, v5e) == pytest.approx(share / 2)
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")


def test_every_seed_offers_the_same_work_in_another_order():
    import numpy as np

    from benchmarks.traffic import open_loop_schedule

    traffic = json.loads(
        (ROOT / "benchmarks/traffic/n16-b4k.steady.json").read_text()
    )
    due_a, arr_a = open_loop_schedule(traffic, 64, 1, 4.0)
    due_b, arr_b = open_loop_schedule(traffic, 64, 2**31 + 5, 4.0)
    assert len(due_a) == len(due_b) == round(traffic["rate_tx_per_s"] * 4.0)
    assert due_a[-1] == pytest.approx(4.0) and due_b[-1] == pytest.approx(4.0)
    gaps_a = np.sort(np.diff(due_a, prepend=0.0))
    gaps_b = np.sort(np.diff(due_b, prepend=0.0))
    assert np.allclose(gaps_a, gaps_b)
    assert not np.allclose(due_a, due_b)
    assert sorted(a.fee for a in arr_a) == sorted(a.fee for a in arr_b)
    assert len({a.tx for a in arr_a}) == len(arr_a)
    assert all(len(a.tx) == 64 for a in arr_a)
    again, _ = open_loop_schedule(traffic, 64, 1, 4.0)
    assert np.array_equal(due_a, again)


def test_benchmark_json_names_files_that_exist():
    from benchmarks import spec

    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for row in bench["workloads"]:
        cell = spec.load_cell(row["name"])
        assert cell.end_to_end and cell.per_layer
        assert any(m["name"] == "setup_s" for m in cell.end_to_end)
        for metric in cell.per_layer:
            assert callable(spec.load_reader(metric["name"]))
    for cfg in bench["configs"]:
        data = json.loads((ROOT / cfg["file"]).read_text())
        assert sorted(data["reduced"]) == sorted(cfg["reduced"])


# -- long rounds: the trace, the drain, the warm-up ------------------------------


@pytest.mark.parametrize("round_s,start_s,limit_s", [
    (0.7, 42.5, 60.0),  # n128-b10k-lockstep: the rule it always had
    (1.0, 42.5, 60.0),  # n16-b4k: the same
    (19.0, 26.0, 114.0),  # n64-b10k: one round before the end, six to drain
])
def test_trace_start_and_drain_limit_follow_the_longest_round(
    round_s, start_s, limit_s
):
    from benchmarks import executors, run

    assert run.trace_start_s(45.0, round_s) == pytest.approx(start_s)
    assert executors.drain_limit_s(round_s) == pytest.approx(limit_s)
    # boundaries lie at most one longest round apart, so one of them
    # falls between the start and the window's nominal end
    boundaries = [i * round_s for i in range(1, int(45 / round_s) + 2)]
    assert any(start_s <= b < 45.0 for b in boundaries)


def test_before_any_round_the_rules_are_the_old_ones():
    from benchmarks import executors, run

    assert executors.RoundClock(None).longest_s == 0.0
    assert run.trace_start_s(45.0, 0.0) == 45.0 - run.TRACE_SECONDS
    assert run.trace_start_s(1.0, 0.0) == 0.0
    assert executors.drain_limit_s(0.0) == executors.DRAIN_LIMIT_S


def test_round_clock_leaves_out_a_round_that_compiled():
    import time

    from benchmarks import executors

    class Meter:
        count = 0

    meter = Meter()
    clock = executors.RoundClock(meter)
    with clock.timed():
        time.sleep(0.01)
    with clock.timed():
        meter.count += 1  # the compiler ran inside this one
        time.sleep(0.1)
    with clock.timed():
        pass
    assert 0.01 <= clock.longest_s < 0.1


def _boundary_the_trace_starts_at(monkeypatch, seconds, warm_round_s, ticks):
    from benchmarks import run

    tracer = run.Tracer(True, seconds, None, "toy", dict, warm_round_s)
    started = []

    def start():
        tracer.started = True
        started.append(tracer._last_tick)

    monkeypatch.setattr(tracer, "_start", start)
    monkeypatch.setattr(tracer, "_probe", lambda: None)
    for now in ticks:
        tracer.tick(now)
    return started


@pytest.mark.parametrize("seconds,warm_round_s,round_s,want", [
    # 19 s rounds, nothing known from warm-up: boundaries at 0, 19, 38;
    # the old rule (now >= 42.5) never starts, this one starts at 38
    (45.0, 0.0, 19.0, 38.0),
    (45.0, 0.9, 0.9, 43.2),  # short rounds: the rule as it was
    (45.0, 0.0, 0.7, 42.7),
    # a window shorter than the round warm-up timed: the first boundary
    (6.0, 19.0, 19.0, 0.0),
])
def test_tracer_starts_at_a_boundary_one_longest_round_before_the_end(
    monkeypatch, seconds, warm_round_s, round_s, want
):
    ticks = [i * round_s for i in range(int(seconds / round_s) + 1)]
    started = _boundary_the_trace_starts_at(
        monkeypatch, seconds, warm_round_s, ticks
    )
    assert started == [pytest.approx(want)]


def test_rounds_longer_than_the_least_trace_are_traced_whole(
    harness, toy_root, monkeypatch, capsys
):
    """With the least length at a millisecond no boundary falls within
    it of the window's end; the trace starts one round before the end
    all the same, covers whole rounds, and the span readers read it."""
    monkeypatch.setattr(harness, "TRACE_SECONDS", 0.001)
    result = harness.run_cell(
        "toy-served.saturated", 2**31 + 23, 1.0, True, root=toy_root
    )
    assert result["correct"] is True, result["compared"]
    device = result["device"]
    assert device["window_s"] > 0.001 and device["busy_s"] >= 0
    assert "breakdown" in result
    metrics = result["metrics"]
    for name in ("hub_self_pct", "router_banks_pct", "hb_turn_pct",
                 "codec_mac_pct", "span_coverage_pct", "round_ms"):
        assert metrics[name]["value"] > 0, name
    said = [
        line for line in capsys.readouterr().out.splitlines()
        if line.startswith("[bench] traced")
    ]
    assert len(said) == 1
    rounds = int(re.search(r": (\d+) whole rounds", said[0]).group(1))
    assert rounds >= 1
    # whole rounds: no shorter than that many of the window's mean round
    # would be if every round were half the mean
    assert device["window_s"] >= 0.5 * rounds * metrics["round_ms"]["value"] / 1e3


def _warm_up_submissions(harness, toy_root, cell):
    seen = {}

    def look(executor):
        seen["submitted"] = len(executor.submissions)
        seen["rounds"] = executor.rounds

    harness.run_cell(cell, 7, 0.5, False, root=toy_root, fault=look)
    return seen


def test_warm_up_fills_come_from_the_configuration_or_stay(harness, toy_root):
    from benchmarks import executors

    default = _warm_up_submissions(harness, toy_root, "toy-served.saturated")
    assert default["submitted"] == sum(
        int(fill * 64) for fill in executors.WARMUP_FILLS
    )
    cfg = json.loads(
        (toy_root / "benchmarks/configs/toy-served.json").read_text()
    )
    assert "warm_up_fills" not in cfg
    # n64-b10k's file gives its own (and warm_shapes, here cut to toys)
    add_config(toy_root, "toy-served-own", "n64-b10k",
               {"n": 4, "batch_size": 64})
    add_cell(toy_root, "toy-served-own.saturated", "n64-b10k.saturated",
             "toy-served-own")
    fills = json.loads(
        (toy_root / "benchmarks/configs/toy-served-own.json").read_text()
    )["warm_up_fills"]
    assert 0 < len(fills) < len(executors.WARMUP_FILLS)
    own = _warm_up_submissions(harness, toy_root, "toy-served-own.saturated")
    assert own["submitted"] == sum(int(fill * 64) for fill in fills)
    assert own["rounds"] < default["rounds"]


def test_device_item_share_by_family_and_the_round():
    from benchmarks import spec

    before = {"modexp_12x22.comb": {"device_items": 10, "host_items": 10}}
    after = {
        "modexp_12x22.comb": {"device_items": 810, "host_items": 110},
        "modexp_12x22.dual_pow": {"device_items": 100, "host_items": 0},
        "merkle.verify_branches": {"device_items": 0, "host_items": 4096},
        "sha256.hash_batch": {"device_items": 0, "host_items": 50},
    }
    run = {
        "counters": {"before": {"placement": before},
                     "after": {"placement": after}},
        "t0": 10.0, "t_end": 67.0, "rounds_in_window": 3,
    }
    read = {
        name: spec.load_reader(name)
        for name in ("modexp_device_item_pct", "merkle_device_item_pct",
                     "rs_device_item_pct", "device_item_pct", "round_ms")
    }
    assert read["modexp_device_item_pct"](run) == pytest.approx(90.0)
    assert read["merkle_device_item_pct"](run) == 0.0  # saw items, sent none
    assert read["rs_device_item_pct"](run) is None  # saw none: left out
    assert read["device_item_pct"](run) == pytest.approx(
        100 * 900 / (900 + 100 + 4096 + 50)
    )
    assert read["round_ms"](run) == pytest.approx(19_000.0)
    assert read["round_ms"]({"t0": 0.0, "t_end": 1.0}) is None


def test_a_configuration_may_fix_the_keys_and_then_seeds_share_them(
    harness, toy_root
):
    """``cluster.key_seed`` in the file: every --seed deals the same
    threshold keys (the same coin, so the same BBA round counts) and
    still makes its own transactions; without it the keys follow
    --seed."""
    cfg_path = toy_root / "benchmarks/configs/toy-served.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["name"] = "toy-served-keyed"
    cfg["cluster"]["key_seed"] = 5
    (toy_root / "benchmarks/configs/toy-served-keyed.json").write_text(
        json.dumps(cfg)
    )
    bench = json.loads((toy_root / "BENCHMARK.json").read_text())
    bench["configs"].append({
        "name": "toy-served-keyed", "source": "toy", "reduced": [],
        "file": "benchmarks/configs/toy-served-keyed.json", "why": "a test's",
    })
    (toy_root / "BENCHMARK.json").write_text(json.dumps(bench))
    add_cell(toy_root, "toy-served-keyed.saturated", "n16-b4k.saturated",
             "toy-served-keyed")

    def look(cell, seed):
        seen = {}

        def fault(executor):
            keys = executor.cluster.keys[executor.ids[0]]
            seen["coin"] = keys.coin_pub.master
            seen["tx"] = executor.submissions[0][0]

        harness.run_cell(cell, seed, 0.3, False, root=toy_root, fault=fault)
        return seen

    a = look("toy-served-keyed.saturated", 11)
    b = look("toy-served-keyed.saturated", 12)
    assert a["coin"] == b["coin"] and a["tx"] != b["tx"]
    c = look("toy-served.saturated", 11)
    d = look("toy-served.saturated", 12)
    assert c["coin"] != d["coin"]
