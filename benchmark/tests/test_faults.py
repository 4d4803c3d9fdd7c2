"""A run with the timed path broken underneath must read ``correct``
false.  Each case drives a whole run (set-up, window, check) at the
configuration's tiny size on the CPU (``--rehearse`` skips the look for
a chip) with one fault planted in the program where it produces its
answer."""
import json

import pytest


def _run(capsys, workload, seed):
    import run

    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", "2", "--trace", "0", "--rehearse"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and out
    return json.loads(out[-1])


def _alter_status(monkeypatch):
    """Every delivered table has its first row's status altered."""
    import pyarrow as pa

    from logparser_tpu.tpu.batch import BatchResult

    orig = BatchResult.to_arrow
    col = "STRING:request.status.last"

    def to_arrow(self, *a, **kw):
        t = orig(self, *a, **kw)
        if t.num_rows and col in t.column_names:
            vals = t.column(col).to_pylist()
            vals[0] = "299"
            t = t.set_column(t.column_names.index(col), col,
                             pa.array(vals, pa.string()))
        return t

    monkeypatch.setattr(BatchResult, "to_arrow", to_arrow)


def _halve_tables(monkeypatch):
    """Every delivered table leaves out the second half of its rows."""
    from logparser_tpu.tpu.batch import BatchResult

    orig = BatchResult.to_arrow
    monkeypatch.setattr(BatchResult, "to_arrow",
                        lambda self, *a, **kw: orig(self, *a, **kw).slice(
                            0, max(1, self.lines_read // 2)))


def _halve_job_batches(monkeypatch):
    """The job folds only the first half of each batch's lines."""
    from logparser_tpu.jobs import runner

    orig = runner._fold_result
    monkeypatch.setattr(
        runner, "_fold_result",
        lambda shard, b, src, result, acc, reg: orig(
            shard, b, src, result.slice(0, result.lines_read // 2), acc, reg))


def _shed_in_window(monkeypatch):
    """Once the window opens, the service sheds every 10th request with
    its own structured BUSY answer (load shedding)."""
    import run
    from logparser_tpu.service import _ServiceServer

    state = {"on": False, "n": 0}
    admit = _ServiceServer.admit_request

    def shedding(self):
        if state["on"]:
            state["n"] += 1
            if state["n"] % 10 == 0:
                return "planted"
        return admit(self)

    load = run.load_module

    def loading(path, name):
        mod = load(path, name)
        window = mod.Driver.window

        def opened(self, seconds):
            state["on"] = True
            return window(self, seconds)

        mod.Driver.window = opened  # a fresh module each run
        return mod

    monkeypatch.setattr(_ServiceServer, "admit_request", shedding)
    monkeypatch.setattr(run, "load_module", loading)


@pytest.mark.parametrize("workload,fault,reads", [
    ("etl.apache_combined.rows", _alter_status, "row_mismatches"),
    ("etl.apache_combined.rows", _halve_job_batches, "count_gaps"),
    ("serve.nginx_combined.shippers", _alter_status, "row_mismatches"),
    ("serve.nginx_combined.shippers", _halve_tables, "row_mismatches"),
    ("serve.nginx_combined.shippers", _shed_in_window, "unanswered"),
])
def test_a_planted_fault_reads_incorrect(capsys, monkeypatch, workload, fault,
                                         reads):
    fault(monkeypatch)
    line = _run(capsys, workload, 2**36 + 11)
    assert line["correct"] is False
    assert line["checks"][reads]["value"] > line["checks"][reads]["limit"]


def test_the_same_run_unbroken_reads_correct(capsys):
    line = _run(capsys, "etl.apache_combined.rows", 2**36 + 11)
    assert line["correct"] is True
