"""The benchmark's data: BENCHMARK.json, cells, configurations, mixes,
and the traffic each mix generates."""

import json
import pathlib
import re
import sys

import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import model, traffic  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
CELLS = [c["name"] for c in BENCH["workloads"]]
MIXES = sorted(p.stem for p in (ROOT / "bench" / "traffic").glob("*.json"))


def _names():
    for sec in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[sec]:
            yield entry["name"]
    for cell in BENCH["workloads"]:
        yield cell["config"]
        yield cell["traffic"]
    for conf in BENCH["configs"]:
        yield from conf["reduced"]


@pytest.mark.parametrize("name", sorted(set(_names())))
def test_names_use_allowed_characters(name):
    assert NAME.match(name), name


@pytest.mark.parametrize("metric", BENCH["end_to_end"] + BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_entries(metric):
    assert UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert (ROOT / "bench" / "metrics" / f"{metric['name']}.py").is_file()
    for cell in metric.get("workloads", CELLS):
        assert cell in CELLS
    if "moves" in metric:
        e2e = {m["name"]: m for m in BENCH["end_to_end"]}
        moved = e2e[metric["moves"]]
        assert set(metric["workloads"]) <= set(moved.get("workloads",
                                                          CELLS))
        assert len(metric["layer"].splitlines()) == 1


def test_names_are_unique_and_bounds_in_range():
    for sec in ("configs", "workloads"):
        names = [e["name"] for e in BENCH[sec]]
        assert len(names) == len(set(names))
    metrics = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(metrics) == len(set(metrics))
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert "setup_s" in metrics
    assert 1 <= BENCH["run_seconds"] <= 51
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_to_files(cell):
    conf = next(c for c in BENCH["configs"] if c["name"] == cell["config"])
    assert (ROOT / conf["file"]).is_file()
    assert conf["file"] == f"bench/configs/{cell['config']}.json"
    assert (ROOT / "bench" / "traffic" / f"{cell['traffic']}.json").is_file()
    assert (ROOT / "bench" / "limits" / f"{cell['name']}.json").is_file()
    assert cell["chips"] in (1, 4)
    assert len(cell["why"]) <= 200
    spec = model.spec_of(model.load_config(cell["config"]))
    assert spec.heads % spec.kv_heads == 0


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_every_cell_reports_setup_another_e2e_and_a_layer_metric(cell):
    def reports(m):
        return "workloads" not in m or cell["name"] in m["workloads"]
    e2e = [m["name"] for m in BENCH["end_to_end"] if reports(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert any(reports(m) for m in BENCH["per_layer"])


@pytest.mark.parametrize("conf", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_states_its_cut(conf):
    data = json.loads((ROOT / conf["file"]).read_text())
    assert data["name"] == conf["name"]
    assert data["source"] == conf["source"]
    assert sorted(data["reduced"]) == sorted(conf["reduced"])
    for key, cut in data["reduced"].items():
        assert data[key] == cut["here"] != cut["published"]


@pytest.mark.parametrize("mix", MIXES)
def test_schedule_is_deterministic_per_seed(mix):
    m = traffic.load_mix(mix)
    a = traffic.schedule(m, 1000, 2 ** 33 + 17, 40.0)
    b = traffic.schedule(m, 1000, 2 ** 33 + 17, 40.0)
    c = traffic.schedule(m, 1000, 5, 40.0)
    assert [(x.due, x.max_new, x.prompt.tolist()) for x in a] == \
        [(x.due, x.max_new, x.prompt.tolist()) for x in b]
    assert [x.prompt.tolist() for x in a] != [x.prompt.tolist() for x in c]


@pytest.mark.parametrize("mix", MIXES)
def test_schedule_respects_clips_and_keeps_the_work_fixed(mix):
    m = traffic.load_mix(mix)
    pl, ol = m["prompt_tokens"], m["output_tokens"]
    runs = [traffic.schedule(m, 1000, s, 40.0) for s in (1, 2, 3)]
    for items in runs:
        assert all(pl["min"] <= len(x.prompt) <= pl["max"] for x in items)
        assert all(ol["min"] <= x.max_new <= ol["max"] for x in items)
        assert all(0 <= x.prompt.min() and x.prompt.max() < 1000
                   for x in items)
        dues = [x.due for x in items]
        assert dues == sorted(dues) and 0 <= dues[0] and dues[-1] < 40.0
    # every seed: the same multiset of lengths and arrival times' count
    key = lambda items: (sorted(len(x.prompt) for x in items),
                         sorted(x.max_new for x in items), len(items),
                         sum(x.greedy for x in items))
    assert key(runs[0]) == key(runs[1]) == key(runs[2])


def test_poisson_gaps_fill_the_window_at_the_rate():
    m = {"arrivals": {"process": "poisson", "rate_per_s": 2.5},
         "prompt_tokens": {"median": 10, "sigma": 0.5, "min": 4, "max": 40},
         "output_tokens": {"median": 5, "sigma": 0.5, "min": 2, "max": 9},
         "sampling": {"temperature": 0.8, "top_k": 50, "greedy_every": 4}}
    items = traffic.schedule(m, 50, 3, 40.0)
    assert len(items) == 100
    assert sum(x.greedy for x in items) == 25
    gaps = np.diff([0.0] + [x.due for x in items])
    assert gaps.max() > 5 * np.median(gaps)           # exponential tail
    assert items[-1].due == pytest.approx(40.0 * (1 - 0.5 / 100))


@pytest.mark.parametrize("n,block", [(16, 4), (50, 8), (17, 4), (3, 8)])
def test_stratified_gaps_take_one_of_each_stratum_per_block(n, block):
    order = traffic.stratified_order(n, block, np.random.default_rng(n))
    assert sorted(order.tolist()) == list(range(n))
    strata = np.array_split(np.arange(n), block)
    stratum_of = {int(r): i for i, st in enumerate(strata) for r in st}
    for j in range(0, n, block):
        seen = [stratum_of[int(r)] for r in order[j:j + block]]
        assert len(set(seen)) == len(seen)
    m = {"arrivals": {"process": "poisson", "rate_per_s": n / 40.0,
                      "block": block},
         "prompt_tokens": {"median": 10, "sigma": 0.5, "min": 4, "max": 40},
         "output_tokens": {"median": 5, "sigma": 0.5, "min": 2, "max": 9},
         "sampling": {"temperature": 0.8, "top_k": 50, "greedy_every": 4}}
    dues = [[x.due for x in traffic.schedule(m, 50, s, 40.0)]
            for s in (1, 2)]
    gaps = [np.diff([0.0] + d) for d in dues]
    assert sorted(gaps[0]) == pytest.approx(sorted(gaps[1]))
    assert dues[0][-1] == pytest.approx(40.0 * (1 - 0.5 / n))


@pytest.mark.parametrize("mix", MIXES)
def test_follow_on_load_is_due_after_the_window(mix):
    m = traffic.load_mix(mix)
    window = traffic.schedule(m, 1000, 7, 40.0)
    after = traffic.follow_on(m, 1000, 7, 40.0, 60.0, len(window))
    if m["arrivals"]["process"] != "poisson":
        assert after == []
        return
    assert [x.index for x in after] == list(
        range(len(window), len(window) + len(after)))
    assert all(40.0 <= x.due < 100.0 for x in after)
    assert len(after) == int(m["arrivals"]["rate_per_s"] * 60.0)
    # a draw of its own: not the window's prompts again
    assert after[0].prompt.tolist() != window[0].prompt.tolist()


def test_lognormal_lengths_and_dealing():
    v = traffic.lognormal_lengths(101, 256, 0.7, 64, 1024)
    assert v[50] == 256 and v.min() == 64 and v.max() == 1024
    order = traffic.vdc_order(8)
    assert order.tolist() == [0, 4, 2, 6, 1, 5, 3, 7]
    dealt = traffic.dealt(np.arange(16), np.random.default_rng(0))
    assert sorted(dealt.tolist()) == list(range(16))
    with pytest.raises(ValueError):
        traffic.rng_for(-1)
