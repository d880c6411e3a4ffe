"""The covertype-lossguide-bulk cell (non-symmetric trees) at tiny sizes
on the CPU: a sound run is correct; the bfloat16 control, an altered
answer and an unwritten chunk are not; a program without node tables
fails before any data is made; the cell's readers read the traced
node_index op."""
import json

import jax
import numpy as np
import pytest

from harness import data, model_nodes, reference, reference_nodes, runner
from harness import spec
from harness.trace import Event, Summary
from harness.work import Run, dims

from conftest import BENCH

CELL = "covertype-lossguide-bulk"
CONFIG = "covertype_lossguide.json"
SEED = 2**33 + 29            # wider than 32 bits, as a check's seeds may be


@pytest.fixture
def root(tiny_root):
    """The tiny benchmark with the node-table configuration cut too:
    few rows and trees, its tree shape (31 leaves, depth <= 8) kept."""
    path = tiny_root / "bench" / "configs" / CONFIG
    cfg = json.loads(path.read_text())
    cfg["data"]["rows"] = 1500
    cfg["model"].update(trees=24, border_sample_rows=500)
    path.write_text(json.dumps(cfg))
    return tiny_root


def _run(root, trace=False):
    cell = spec.load_cell(CELL, root=root)
    return runner.run_cell(cell, SEED, 0.5, trace, jax.devices(), 0.0)


def test_cell_is_read_from_the_benchmark():
    cell = spec.load_cell(CELL)
    assert (cell.config_name, cell.traffic_name, cell.chips) == \
        ("covertype-lossguide", "bulk_sweep_nodes", 1)
    assert [m["name"] for m in cell.end_to_end] == ["rows_per_s", "setup_s"]
    bulk = [m["name"] for m in spec.load_cell("covertype-bulk").per_layer]
    assert [m["name"] for m in cell.per_layer] == \
        bulk + ["node_index_roofline", "bulk.node_index_ms"]


def test_generated_trees_are_lossguide_shaped():
    cfg = json.loads((BENCH / "configs" / CONFIG).read_text())
    cfg["data"]["rows"] = 2000
    cfg["model"].update(trees=200, border_sample_rows=500)
    x, _ = data.generate(cfg, SEED)
    m = model_nodes.random_node_ensemble(cfg, x, SEED)
    assert m["node_left"].shape == (200, 30)
    assert m["leaf_values"].shape == (200, 31, 7)
    # every leaf id is reached once, by a walk of at most 8 steps
    idx = reference_nodes.leaf_index(m, x[:300])
    assert idx.min() >= 0 and idx.max() <= 30
    leaves = np.concatenate([m["node_left"], m["node_right"]], axis=1)
    for row in leaves:
        np.testing.assert_array_equal(np.sort(-1 - row[row < 0]),
                                      np.arange(31))
    again = model_nodes.random_node_ensemble(cfg, x, SEED)
    np.testing.assert_array_equal(again["node_bins"], m["node_bins"])


def test_sound_run_is_correct(root, capsys):
    line = _run(root)
    assert line["correct"] is True, line
    assert line["failed"] == 0 and line["attempted"] > 0
    assert set(line["metrics"]) == {"rows_per_s", "setup_s"}
    assert line["checks"]["proba_max_abs_err"]["value"] < 1e-6
    setup = [json.loads(x) for x in capsys.readouterr().out.splitlines()
             if '"phase": "setup"' in x][0]
    assert setup["plan"]["layout"] == "nodes"
    assert setup["plan"]["lowered"]["nodes_per_tree"] == 30
    assert {"node_index", "leaf_gather"} <= set(setup["impls"])


def test_control_in_programs_place_is_not_correct(root, monkeypatch):
    """At the configuration's own tree shape and 2,000 trees, the
    bfloat16 control answers for the scorer and fails the limit."""
    from types import SimpleNamespace

    from repro.scoring.scorer import BulkScorer

    path = root / "bench" / "configs" / CONFIG
    cfg = json.loads(path.read_text())
    cfg["model"]["trees"] = 2000
    path.write_text(json.dumps(cfg))
    tr_path = root / "bench" / "traffic" / "bulk_sweep_nodes.json"
    tr = json.loads(tr_path.read_text())
    tr["check_rows"] = 256
    tr_path.write_text(json.dumps(tr))
    made = {}
    real = model_nodes.random_node_ensemble

    def capture(config, x, seed):
        made["m"] = real(config, x, seed)
        return made["m"]

    def score(self, source, sinks=None, **_kw):
        n, chunk = source.n_rows, self.resolve_chunk_rows(source.n_rows)
        if sinks is not None:
            sinks.open(n, 7)
        for s in range(0, n, chunk):
            raw = reference_nodes.control_raw(
                made["m"], source.read(s, min(s + chunk, n)))
            if sinks is not None:
                sinks.write(s, reference.proba(raw).astype(np.float32))
        if sinks is not None:
            sinks.close()
        return SimpleNamespace(n_rows=n, metrics={
            "chunks": -(-n // chunk), "quantize_s": 0.0, "wall_s": 0.0})

    monkeypatch.setattr(model_nodes, "random_node_ensemble", capture)
    monkeypatch.setattr(BulkScorer, "score", score)
    line = _run(root)
    c = line["checks"]["proba_max_abs_err"]
    assert line["correct"] is False, line
    assert c["limit"] < c["value"] < 1.0, c
    assert line["failed"] == 0


def test_altered_answers_are_not_correct(root, monkeypatch):
    from repro.core.predictor import Predictor

    real = Predictor.proba

    def altered(self, x):
        p = np.array(real(self, x), np.float32)
        p[:, 0] += 1e-3
        p[:, 1] -= 1e-3
        return p
    monkeypatch.setattr(Predictor, "proba", altered)
    line = _run(root)
    assert line["correct"] is False
    c = line["checks"]["proba_max_abs_err"]
    assert c["value"] > c["limit"]


def test_unwritten_chunk_is_not_correct(root, monkeypatch):
    from repro.scoring.sinks import NpySink

    real = NpySink.write

    def drop_first(self, start, scores):
        if start != 0:
            real(self, start, scores)
    monkeypatch.setattr(NpySink, "write", drop_first)
    line = _run(root)
    assert line["correct"] is False
    assert line["failed"] > 0 and line["checks"]["bad_rows"]["value"] > 0


def test_program_without_node_tables_fails_before_any_data(root,
                                                           monkeypatch):
    from repro.core import trees

    def no_data(*_a, **_k):
        raise AssertionError("data made before the program was checked")
    monkeypatch.delattr(trees, "NodeEnsemble")
    monkeypatch.setattr(data, "generate", no_data)
    with pytest.raises(ImportError):
        _run(root)


def _traced_run(ops, counters):
    """A run whose traced sweep held `ops`, over 512 rows."""
    cfg = json.loads((BENCH / "configs" / CONFIG).read_text())
    summary = Summary(window_s=10e-3, busy_s=8e-3, ops=ops, gaps=[],
                      n_devices=1)
    return Run(BENCH, dims(cfg), {"ops_per_s": 197e12,
                                  "hbm_bytes_per_s": 819e9},
               {}, counters, summary, traced_rows=512)


def test_readers_match_the_raw_node_index_op_name():
    """The traced sweep's node_index op, named as the trace's "XLA Ops"
    line names it (HLO text with a leading "%"), over two 256-row
    chunks: its time per chunk, and a roofline share under 100%."""
    ms = 1e6
    ops = [Event("%node_index.1 = s32[10048,256]{1,0} custom-call(...)",
                 0, 0.4 * ms),
           Event("%node_index.1 = s32[10048,256]{1,0} custom-call(...)",
                 3 * ms, 0.4 * ms),
           Event("%leaf_gather.1 = f32[8,256]{1,0} custom-call(...)",
                 0.5 * ms, 2.0 * ms)]
    run = _traced_run(ops, {"chunk_rows": 256, "nodes_per_tree": 30})
    per_chunk = spec.metric_reader(spec.load_cell(CELL),
                                   "bulk.node_index_ms").read(run)
    assert per_chunk == pytest.approx(0.4)
    share = spec.metric_reader(spec.load_cell(CELL),
                               "node_index_roofline").read(run)
    ops_, nbytes = spec.kernel(BENCH, "node_index").work(
        {**run.dims, "nodes_per_tree": 30}, 512, 2)
    assert ops_ == 512 * 10000 * 8
    assert nbytes == 512 * (54 + 40000) + 2 * 10000 * 30 * 8
    assert share == pytest.approx(
        100 * max(ops_ / 197e12, nbytes / 819e9) / 0.8e-3)
    assert 0 < share < 100


def test_readers_read_nothing_without_the_op():
    ops = [Event("%leaf_gather.1 = f32[8,256]{1,0} custom-call(...)",
                 0, 2e6)]
    run = _traced_run(ops, {"chunk_rows": 256})
    for name in ("bulk.node_index_ms", "node_index_roofline"):
        reader = spec.metric_reader(spec.load_cell(CELL), name)
        assert reader.read(run) is None
        assert reader.read(Run(BENCH, run.dims, {}, {}, {})) is None
