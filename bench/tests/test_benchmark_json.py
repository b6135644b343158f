"""BENCHMARK.json keeps to its contract, and every name in it finds its
file."""

import re

import pytest

from bench import harness

BM = harness.benchmark()
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert 1 <= BM["run_seconds"] <= 51
    assert all(not w.startswith("/") and ".." not in w
               for w in BM["command"])


def test_names_and_units_use_only_allowed_characters():
    names = [m["name"] for m in BM["end_to_end"] + BM["per_layer"]]
    names += [w["name"] for w in BM["workloads"]]
    names += [c["name"] for c in BM["configs"]]
    names += [w["traffic"] for w in BM["workloads"]]
    for c in BM["configs"]:
        names += c["reduced"]
    for n in names:
        assert NAME.fullmatch(n), n
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.fullmatch(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    assert len({m["name"] for m in BM["end_to_end"] + BM["per_layer"]}) == \
        len(BM["end_to_end"]) + len(BM["per_layer"])


def test_every_metric_workload_reports_what_it_moves():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    cells = {w["name"] for w in BM["workloads"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BM["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BM["per_layer"]:
        moved = e2e[m["moves"]]
        for w in m.get("workloads", []):
            assert w in cells
            assert "workloads" not in moved or w in moved["workloads"], \
                (m["name"], w)
        assert m["name"].endswith("_roofline") == ("roofline" in m["name"])
    for w in cells:
        cell = harness.load_cell(w)
        assert any(m["name"] != "setup_s" for m in cell.end_to_end), w
        assert cell.per_layer, w


def test_each_name_finds_its_file():
    for c in BM["configs"]:
        assert (harness.ROOT / c["file"]).is_file()
        cfg = harness.load_json(harness.ROOT / c["file"])
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
    for w in BM["workloads"]:
        cell = harness.load_cell(w["name"])
        assert (harness.BENCH / "drivers"
                / f"{cell.traffic['kind']}.py").is_file()
    for m in BM["per_layer"]:
        assert callable(harness.layer_reader(m["name"]))


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_four_chips_only_where_chips_exchange(cell):
    w = next(x for x in BM["workloads"] if x["name"] == cell)
    assert w["chips"] in (1, 4)
    assert (w["chips"] == 4) == (cell == "halo-x4")


def test_a_metric_without_workloads_goes_where_its_moves_goes():
    metric = {"name": "x", "moves": "train_tokens_per_s"}
    assert harness._reports(metric, "train-2k", {"train_tokens_per_s",
                                                 "setup_s"})
    assert not harness._reports(metric, "halo-x1", {"halo_sweeps_per_s",
                                                    "setup_s"})
    assert not harness._reports(dict(metric, workloads=["halo-x1"]),
                                "train-2k", {"train_tokens_per_s"})


def test_each_pair_of_config_and_traffic_is_given_once():
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(set(pairs)) == len(pairs), pairs
    assert len({w["name"] for w in BM["workloads"]}) == len(BM["workloads"])
    assert len({c["name"] for c in BM["configs"]}) == len(BM["configs"])
    assert len({c["file"] for c in BM["configs"]}) == len(BM["configs"])
    assert {c["name"] for c in BM["configs"]} == \
        {w["config"] for w in BM["workloads"]}


def test_entries_hold_only_the_keys_of_the_contract():
    keys = {"configs": {"name", "source", "file", "reduced", "why"},
            "workloads": {"name", "config", "traffic", "chips", "why"},
            "end_to_end": {"name", "unit", "better", "bound", "source"},
            "per_layer": {"name", "unit", "better", "source", "layer",
                          "moves"}}
    for group, want in keys.items():
        for entry in BM[group]:
            extra = {"workloads"} if group in ("end_to_end",
                                               "per_layer") else set()
            assert want <= set(entry) <= want | extra, entry


def test_free_text_fits_one_line_of_200_characters():
    texts = [c["source"] for c in BM["configs"]]
    texts += [e["why"] for e in BM["configs"] + BM["workloads"]]
    texts += [m["layer"] for m in BM["per_layer"]] + BM["command"]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t, t


def test_the_full_check_fits_its_time_at_24_cells():
    runs = 2 + 14 * 24
    assert runs * (BM["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert four <= max(1, len(BM["workloads"]) // 2)
