"""Whole runs at tiny sizes on the CPU, past the look for a chip: sound
runs come out correct, runs with the timed path broken come out not
correct, and a cell added as files only is found and run."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from harness import runner, spec

from conftest import BENCH, ROOT, TINY

SEED = 2**33 + 17            # wider than 32 bits, as a check's seeds may be


def _run(root, workload, trace=False):
    cell = spec.load_cell(workload, root=root)
    return runner.run_cell(cell, SEED, 0.5, trace, jax.devices(), 0.0)


@pytest.mark.parametrize("workload", ["covertype-bulk", "santander-bulk"])
def test_bulk_cell_sound_run_is_correct(tiny_root, workload):
    line = _run(tiny_root, workload)
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    assert list(line)[-1] == "checks"
    assert line["checks"]["proba_max_abs_err"]["value"] < 1e-6


@pytest.mark.parametrize("workload", ["covertype-bulk", "santander-bulk"])
def test_bulk_window_line_gives_each_sweep(tiny_root, capsys, workload):
    _run(tiny_root, workload)
    lines = [json.loads(x) for x in capsys.readouterr().out.splitlines()]
    (window,) = [x for x in lines if x.get("phase") == "window"]
    assert len(window["sweep_s"]) == len(window["sweep_rows"]) \
        == window["sweeps"] >= 1
    assert sum(window["sweep_rows"]) == window["rows"]
    assert all(s > 0 for s in window["sweep_s"])
    assert sum(window["sweep_s"]) == pytest.approx(window["seconds"])


def test_bulk_setup_warms_the_timed_path(tiny_root, monkeypatch):
    """Before the window, every row of the table is read through the
    window's NpyMemmapSource, and its last chunks are scored from that
    source into an NpySink by the window's scorer."""
    from repro.scoring.scorer import BulkScorer
    from repro.scoring.sinks import NpySink
    from repro.scoring.sources import NpyMemmapSource

    seen = []
    real_read, real_score = NpyMemmapSource.read, BulkScorer.score

    def read(self, start, stop):
        seen.append(("read", id(self), start, stop))
        return real_read(self, start, stop)

    def score(self, source, sinks=None, **kw):
        seen.append(("score", id(self), id(source), type(source),
                     type(sinks), kw.get("resume_from", 0)))
        return real_score(self, source, sinks, **kw)
    monkeypatch.setattr(NpyMemmapSource, "read", read)
    monkeypatch.setattr(BulkScorer, "score", score)
    _run(tiny_root, "covertype-bulk")

    scores = [x for x in seen if x[0] == "score"]
    warm, first = scores[0], scores[1]
    assert warm[3:5] == (NpyMemmapSource, NpySink) and warm[5] > 0
    assert warm[1:3] == first[1:3] and first[5] == 0
    rows = 0
    for x in seen[:seen.index(warm)]:
        assert x[1] == warm[2] and x[2] == rows
        rows = x[3]
    assert rows == 1500 == TINY["covertype"]["rows"]


def test_online_cell_sound_run_is_correct(tiny_root):
    line = _run(tiny_root, "covertype-online")
    assert line["correct"] is True, line
    assert set(line["metrics"]) == {"p50_ms", "p99_ms", "setup_s"}
    assert line["metrics"]["p99_ms"]["value"] >= \
        line["metrics"]["p50_ms"]["value"] > 0


def _perturb_proba(monkeypatch):
    """Every answer altered where it is produced: probability moved
    from the second class to the first, rows still summing to 1."""
    from repro.core.predictor import Predictor

    real = Predictor.proba

    def altered(self, x):
        p = np.array(real(self, x), np.float32)
        p[:, 0] += 1e-3
        p[:, 1] -= 1e-3
        return p
    monkeypatch.setattr(Predictor, "proba", altered)


@pytest.mark.parametrize("workload", ["covertype-bulk", "santander-bulk",
                                      "covertype-online"])
def test_altered_answers_are_not_correct(tiny_root, monkeypatch, workload):
    _perturb_proba(monkeypatch)
    line = _run(tiny_root, workload)
    assert line["correct"] is False
    c = line["checks"]["proba_max_abs_err"]
    assert c["value"] > c["limit"]


def _control_in_programs_place(monkeypatch):
    """The bfloat16 control (`reference.control_raw`) answers where the
    program would: in the server's model call, and as the whole bulk
    scorer, chunk by chunk into the same sinks."""
    from types import SimpleNamespace

    from harness import model, reference
    from repro.core.predictor import Predictor
    from repro.scoring.scorer import BulkScorer

    made = {}
    real_ensemble = model.random_ensemble

    def capture(config, x, seed):
        made["m"] = real_ensemble(config, x, seed)
        return made["m"]

    def control(x):
        raw = reference.control_raw(made["m"], np.asarray(x, np.float32))
        return reference.proba(raw).astype(np.float32)

    def score(self, source, sinks=None, **_kw):
        n, chunk = source.n_rows, self.resolve_chunk_rows(source.n_rows)
        if sinks is not None:
            sinks.open(n, self._output_width(next(iter(self.plans.values()))))
        for s in range(0, n, chunk):
            p = control(source.read(s, min(s + chunk, n)))
            if sinks is not None:
                sinks.write(s, p)
        if sinks is not None:
            sinks.close()
        return SimpleNamespace(n_rows=n, metrics={
            "chunks": -(-n // chunk), "quantize_s": 0.0, "wall_s": 0.0})

    monkeypatch.setattr(model, "random_ensemble", capture)
    monkeypatch.setattr(Predictor, "proba", lambda self, x: control(x))
    monkeypatch.setattr(BulkScorer, "score", score)


@pytest.mark.parametrize("workload", ["covertype-bulk", "santander-bulk",
                                      "covertype-online"])
def test_control_in_programs_place_is_not_correct(tiny_root, monkeypatch,
                                                   workload):
    """At the configurations' own depth and enough trees (2,000) for
    bfloat16 leaves to show, the control fails `proba_max_abs_err`."""
    for path in (tiny_root / "bench" / "configs").glob("*.json"):
        cfg = json.loads(path.read_text())
        src = json.loads((BENCH / "configs" / path.name).read_text())
        cfg["model"].update(trees=2000, depth=src["model"]["depth"])
        path.write_text(json.dumps(cfg))
    for path in (tiny_root / "bench" / "traffic").glob("*.json"):
        tr = json.loads(path.read_text())
        tr["check_rows"] = 256
        if tr["client"] == "online":
            tr["rate_per_s"] = 600
        path.write_text(json.dumps(tr))
    _control_in_programs_place(monkeypatch)
    line = _run(tiny_root, workload)
    c = line["checks"]["proba_max_abs_err"]
    assert line["correct"] is False, line
    assert c["limit"] < c["value"] < 1.0, c    # answers came, each a bit off
    assert line["failed"] == 0


def test_unwritten_chunk_is_not_correct(tiny_root, monkeypatch):
    from repro.scoring.sinks import NpySink

    real = NpySink.write

    def drop_first(self, start, scores):
        if start != 0:
            real(self, start, scores)
    monkeypatch.setattr(NpySink, "write", drop_first)
    line = _run(tiny_root, "covertype-bulk")
    assert line["correct"] is False
    assert line["failed"] > 0 and line["checks"]["bad_rows"]["value"] > 0


def test_dropped_reply_is_not_correct(tiny_root, monkeypatch):
    from repro.serving import batching

    drv = spec.client(spec.load_cell("covertype-online", root=tiny_root))
    monkeypatch.setattr(drv, "GIVE_UP_S", 0.5)
    real = batching.Batcher.submit
    count = [0]

    def lose_every_50th(self, rid, payload):
        count[0] += 1
        fut = real(self, rid, payload)
        return __import__("queue").Queue() if count[0] % 50 == 0 else fut
    monkeypatch.setattr(batching.Batcher, "submit", lose_every_50th)
    cell = spec.load_cell("covertype-online", root=tiny_root)
    line = runner.run_cell(cell, SEED, 0.5, False, jax.devices(), 0.0)
    assert line["correct"] is False
    assert line["failed"] > 0
    assert line["checks"]["unanswered"]["value"] == line["failed"]


def test_cell_config_mix_and_metric_added_as_files(tiny_root):
    """A new configuration, traffic mix, cell and per-layer metric are
    new files and entries only: the harness finds them by name."""
    bench = tiny_root / "bench"
    cfg = json.loads((bench / "configs" / "covertype.json").read_text())
    cfg["data"].update(features=9, classes=3)
    cfg["model"].update(outputs=3, trees=8, depth=2)
    (bench / "configs" / "newmodel.json").write_text(json.dumps(cfg))
    mix = {"client": "bulk", "output": "proba", "check_rows": 32}
    (bench / "traffic" / "bulk_small.json").write_text(json.dumps(mix))
    (bench / "metrics" / "new.rows_seen.py").write_text(
        "def read(run):\n    return run.counters['chunks'] * 1.0\n")
    spec_path = tiny_root / "BENCHMARK.json"
    b = json.loads(spec_path.read_text())
    b["configs"].append({"name": "newmodel", "source": "test",
                         "file": "bench/configs/newmodel.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "newmodel-bulk", "config": "newmodel",
                           "traffic": "bulk_small", "chips": 1,
                           "why": "test"})
    b["end_to_end"][0]["workloads"].append("newmodel-bulk")
    b["per_layer"].append({"name": "new.rows_seen", "unit": "chunks",
                           "better": "higher", "source": "program_counter",
                           "layer": "scoring", "moves": "rows_per_s",
                           "workloads": ["newmodel-bulk"]})
    spec_path.write_text(json.dumps(b))

    cell = spec.load_cell("newmodel-bulk", root=tiny_root)
    assert [m["name"] for m in cell.per_layer] == ["new.rows_seen"]
    line = _run(tiny_root, "newmodel-bulk")
    assert line["correct"] is True, line
    assert "rows_per_s" in line["metrics"]
    run = runner.Run(bench, runner.dims(cell.config), {}, {},
                     {"chunks": 3})
    assert runner.per_layer(cell, run) == {
        "new.rows_seen": {"value": 3.0, "unit": "chunks"}}


def test_bulk_cells_are_read_from_the_benchmark():
    """Both bulk cells stand in BENCHMARK.json itself and report the
    same metrics."""
    bulk = [spec.load_cell(w) for w in ("covertype-bulk", "santander-bulk")]
    assert [c.config_name for c in bulk] == ["covertype", "santander"]
    assert {c.traffic_name for c in bulk} == {"bulk_sweep"}
    assert [m["name"] for m in bulk[0].end_to_end] == \
        [m["name"] for m in bulk[1].end_to_end] == ["rows_per_s", "setup_s"]
    assert [m["name"] for m in bulk[0].per_layer] == \
        [m["name"] for m in bulk[1].per_layer]
    assert len(bulk[1].per_layer) == 5


def test_unknown_workload_is_refused():
    with pytest.raises(spec.SpecError, match="no workload"):
        spec.load_cell("no-such-cell")


def _cli(cwd, *args):
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, str(cwd / "bench" / "run.py"), *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_no_chip_exits_non_zero_without_a_result():
    p = _cli(ROOT, "--workload", "covertype-bulk", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode == runner.EXIT_NO_CHIP, p.stderr
    assert p.stdout.strip() == ""
    assert "tpu" in p.stderr


def test_bare_checkout_exits_non_zero_without_a_result(tmp_path):
    import shutil

    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    p = _cli(tmp_path, "--workload", "covertype-bulk", "--seed", "1",
             "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""
