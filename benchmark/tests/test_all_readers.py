"""``benchmark/all_readers.py``: the by-hand traced run asks every
per-layer reader ``BENCHMARK.json`` has in the cell it is given, leaves
out the ones that raise or read nothing, keeps the launcher's rows when
asked and prints ``run.py``'s line. The run itself is stood in for (a
traced run needs the chip): what is tested is what the script puts
between ``run_cell`` and the readers."""
import gzip
import json
import os

from benchmark import all_readers
from benchmark.harness import runner

CELL = "keye_vl2_30b_a3b_l4.fedavg_k2_e10"


def test_every_reader_is_asked_and_the_rows_are_kept(tmp_path, monkeypatch,
                                                     capsys):
    names = [m["name"] for m in runner.load_json(
        os.path.join(runner.REPO, "BENCHMARK.json"))["per_layer"]]
    listed = [m["name"] for m in runner.metrics_for(
        runner.load_cell(CELL), "per_layer")]
    assert set(listed) < set(names)
    run_dir = tmp_path / "run"
    run_dir.mkdir()
    (run_dir / "metrics.jsonl").write_text('{"round": 0}\n')
    asked = []

    def run_cell(workload, seed, seconds, trace, t_start=None):
        assert trace
        cell = runner.load_cell(workload)
        assert runner.read_spans(str(run_dir)) \
            == {"origin_unix": None, "spans": []}
        # no trace and no rows: a reader returns None or raises
        ctx = {"cell": cell, "rows": [], "all_rows": [], "trace": None,
               "spans": {"origin_unix": None, "spans": []}}
        for m in runner.metrics_for(cell, "per_layer"):
            asked.append(m["name"])
            assert runner.load_by_name(
                "layer_metrics", m["name"]).read(ctx) is None
        assert [m["name"] for m in runner.metrics_for(cell, "end_to_end")] \
            == ["round_s_p50", "peak_hbm_gib", "setup_s"]
        return {"correct": True, "workload": workload, "seed": seed}

    for name in ("metrics_for", "load_by_name", "read_spans"):
        monkeypatch.setattr(runner, name, getattr(runner, name))
    monkeypatch.setattr(runner, "run_cell", run_cell)
    kept = tmp_path / "out" / "rows.jsonl.gz"
    assert all_readers.main(["--workload", CELL, "--seed", "5",
                             "--seconds", "1", "--rows", str(kept)]) == 0
    assert asked == names
    assert gzip.open(kept, "rt").read() == '{"round": 0}\n'
    out, err = capsys.readouterr()
    assert json.loads(out.splitlines()[-1]) \
        == {"correct": True, "workload": CELL, "seed": 5}
    assert all(f"all_readers: {n} reads nothing" in err for n in names)
