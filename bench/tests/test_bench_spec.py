"""BENCHMARK.json against the benchmark's contract, and the harness being
driven by data: a cell added from data alone resolves and generates."""
import json
import re
import shutil
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

from benchcore import spec, traffic  # noqa: E402

BM = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|projection|_dim$|"
                   r"_rank$|head|expansion|expand|experts_per_tok)")


def test_top_level_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert all(isinstance(w, str) and 0 < len(w) <= 200 and "\n" not in w
               for w in BM["command"]) and len(BM["command"]) <= 32
    assert isinstance(BM["run_seconds"], int) and 1 <= BM["run_seconds"] <= 51
    # a full check of 24 cells fits in 43200 s
    runs = 2 + 14 * 24
    assert runs * (BM["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert len(json.dumps(BM)) <= 64 * 1024


def test_entries_have_exactly_their_keys():
    for c in BM["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in BM["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
    for m in BM["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
    for m in BM["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}


def test_names_units_and_text():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert len(names) == len(set(names))
    for n in names:
        assert NAME.match(n), n
    for w in BM["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    texts = [c["why"] for c in BM["configs"]] + \
        [c["source"] for c in BM["configs"]] + \
        [w["why"] for w in BM["workloads"]] + \
        [m["layer"] for m in BM["per_layer"]]
    for t in texts:
        assert 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t


def test_configs_and_reductions():
    used = {w["config"] for w in BM["workloads"]}
    assert used == {c["name"] for c in BM["configs"]}
    files = [c["file"] for c in BM["configs"]]
    assert len(files) == len(set(files))
    for c in BM["configs"]:
        assert c["file"].startswith("bench/")
        body = json.loads((ROOT / c["file"]).read_text())
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        assert len(c["reduced"]) <= 16
        for k in c["reduced"]:
            assert NAME.match(k) and not WIDTH.search(k), k
        assert c["source"].startswith("https://")


def test_departures_apply_to_the_run():
    """A configuration file keeps its source's numbers under `model`;
    the program's own departures replace them in what is run."""
    body = json.loads((BENCH / "configs" / "granite-3-2b-altup2.json")
                      .read_text())
    assert body["model"]["rms_norm_eps"] == 1e-5
    assert body["model"]["embedding_multiplier"] == 12.0
    cell = spec.resolve("granite-3-2b-altup2.offline")
    assert cell.config["model"]["rms_norm_eps"] == 1e-6
    assert cell.config["model"]["embedding_multiplier"] == 1.0
    assert cell.config["model"]["hidden_size"] == 2048
    qwen = spec.resolve("qwen3-0.6b-altup2.chat").config["model"]
    assert qwen == json.loads((BENCH / "configs" / "qwen3-0.6b-altup2.json")
                              .read_text())["model"]


def test_cells_and_chips():
    pairs = [(w["config"], w["traffic"]) for w in BM["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in BM["workloads"])
    assert all(w["chips"] in (1, 4) for w in BM["workloads"])
    assert four <= max(1, len(BM["workloads"]) // 2)
    for w in BM["workloads"]:
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()


def test_metrics_bounds_and_sources():
    e2e = {m["name"]: m for m in BM["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        assert (BENCH / "metrics" / f"{m['name']}.py").exists()
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_every_cell_reports_enough():
    for w in BM["workloads"]:
        cell = spec.resolve(w["name"])
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        for m in cell.per_layer:
            # a per-layer metric moves an end-to-end metric its cell reports
            assert m["moves"] in names, (w["name"], m["name"])
    layers = {}
    for m in BM["per_layer"]:
        layers.setdefault(m["layer"], m["layer"])
    assert all(len(x) <= 200 for x in layers)


def test_unknown_device_kind_raises():
    from benchcore import driver
    assert driver.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        driver.load_peaks("TPU v99 imaginary")


def _sources():
    return {p: p.read_bytes() for pat in ("*.json", "*.py")
            for p in BENCH.rglob(pat)}


def test_cell_added_from_data_alone(tmp_path):
    """A new workloads entry naming an existing config and mix, with its
    own traffic parameters and a layout override in a cell file of its
    own, resolves to its config, generator and readers and generates its
    requests; and a configuration of a model family the harness has not
    seen resolves to the family's own file. No file of the benchmark
    changes."""
    for d in ("configs", "traffic", "metrics", "cells", "families"):
        shutil.copytree(BENCH / d, tmp_path / "bench" / d)
    tiny = BENCH / "tests" / "data" / "tiny"
    shutil.copy(tiny / "configs" / "tiny-split.json",
                tmp_path / "bench" / "configs")
    shutil.copy(tiny / "families" / "tiny_split.py",
                tmp_path / "bench" / "families")
    before = _sources()
    bm = json.loads(json.dumps(BM))
    name = "qwen3-0.6b-altup2.chat-paged-fast"
    bm["workloads"].append({"name": name, "config": "qwen3-0.6b-altup2",
                            "traffic": "chat", "chips": 1, "why": "test"})
    (tmp_path / "bench" / "cells" / f"{name}.json").write_text(json.dumps({
        "deployment": {"paged": True, "page_size": 32},
        "traffic": {"arrivals": {"rate_per_s": 3.0}},
        "limits": {"max_logit_gap": 1.0, "compared_tokens": 100}}))
    cell = spec.resolve(name, bm, tmp_path / "bench", tmp_path)
    assert cell.deployment["paged"] is True
    assert cell.deployment["page_size"] == 32
    assert cell.deployment["n_slots"] == 8          # the config's own
    assert cell.traffic["arrivals"] == {"kind": "poisson", "rate_per_s": 3.0}
    assert cell.traffic["prompt_tokens"]["median"] == 256
    assert {m["name"] for m in cell.per_layer} >= {"engine_step_ms",
                                                   "step_device_ms"}
    assert all(callable(r) for r in cell.readers.values())
    assert cell.family.shape(cell.config).n_layers == 28
    reqs = traffic.generate(cell.traffic, 5, 20.0, 151936)
    assert 40 <= len(reqs) <= 80

    bm["configs"].append({"name": "tiny-split", "source": "test",
                          "file": "bench/configs/tiny-split.json",
                          "reduced": ["num_hidden_layers"], "why": "test"})
    bm["workloads"].append({"name": "tiny-split.chat", "config": "tiny-split",
                            "traffic": "chat", "chips": 1, "why": "test"})
    cell = spec.resolve("tiny-split.chat", bm, tmp_path / "bench", tmp_path)
    assert Path(cell.family.__file__) == \
        tmp_path / "bench" / "families" / "tiny_split.py"
    params = {"seg0": {"w": np.arange(6.0).reshape(2, 3)}}
    split = cell.family.segments(params, dict(cell.family.arch(cell.config)))
    assert [n for _, n, _ in split] == [1, 1]
    assert cell.family.shape(cell.config).n_layers == 2
    assert _sources() == before


@pytest.mark.parametrize("family", [None, "no_such_family"],
                         ids=["no_family_key", "missing_module"])
def test_config_without_its_family_raises(tmp_path, family):
    """A configuration names its family, and the family's file exists;
    there is no default family."""
    shutil.copytree(BENCH / "families", tmp_path / "families")
    config = json.loads((BENCH / "configs" / "qwen3-0.6b-altup2.json")
                        .read_text())
    if family is None:
        del config["family"]
    else:
        config["family"] = family
    (tmp_path / "c.json").write_text(json.dumps(config))
    bm = {"configs": [{"name": "c", "file": "c.json"}],
          "workloads": [{"name": "c.chat", "config": "c", "traffic": "chat",
                         "chips": 1}],
          "end_to_end": [], "per_layer": []}
    shutil.copytree(BENCH / "traffic", tmp_path / "traffic")
    with pytest.raises((KeyError, FileNotFoundError), match="family"):
        spec.resolve("c.chat", bm, tmp_path, tmp_path)
