"""The five readers of the program's start-up account: a snapshot in, the
values out; nothing, and no error, where the program has no such module (a
commit older than it) or never declared itself ready."""

import sys

import pytest

from benchmarks.harness import manifest, startup_account

READERS = ("startup_ready_s", "startup_caller_share",
           "startup_compile_path_s", "startup_cache_misses",
           "startup_weights_s")
CELLS = ("mistral-7b.train-seq8k", "mistral-7b.serve-docs")

SNAP = {
    "startup/ready_s": 40.0,
    "startup/ms_total/process": 18000.0,
    "startup/ms_total/import": 3000.0,
    "startup/ms_total/backend": 0.0,
    "startup/ms_total/mesh": 500.0,
    "startup/ms_total/weights": 6000.0,
    "startup/ms_total/optimizer": 1500.0,
    "startup/ms_total/engine": 0.0,
    "startup/ms_total/warmup": 0.0,
    "startup/ms_total/step0": 9000.0,
    "startup/ms_total/audit": 2000.0,
    "startup/compile_ms_total/trace": 4000.0,
    "startup/compile_ms_total/lower": 2500.0,
    "startup/compile_ms_total/backend_compile": 250.0,
    "startup/compile_ms_total/cache_read": 1250.0,
    "startup/compile_saved_ms_total": 93000.0,
    "startup/compile_requests_total": 41.0,
    "startup/cache_hits_total": 38.0,
    "startup/cache_misses_total": 3.0,
    "label": "fit",
    "programs": [["_step", 7.25], ["init_sharded", 2.5]],
}


def reader(name, cell):
    return manifest.Cell(cell).layer_metric(name)


@pytest.mark.parametrize("cell", CELLS)
def test_a_snapshot_in_the_values_out(monkeypatch, capsys, cell):
    monkeypatch.setattr(startup_account, "snapshot", lambda: dict(SNAP))
    # the readers take the account from the process, not from the reading
    assert reader("startup_ready_s", cell).read(None) == 40.0
    line = capsys.readouterr().out
    assert line.startswith("[startup] ready (fit) 40.000 s after the process")
    assert "process 18.000, import 3.000, mesh 0.500, weights 6.000, " \
        "optimizer 1.500, step0 9.000, audit 2.000;" in line
    assert "trace 4.000, lower 2.500, backend_compile 0.250, cache_read " \
        "1.250 (saved 93.0); requests 41, hits 38, misses 3; programs " \
        "_step 7.25, init_sharded 2.50" in line
    assert "backend" not in line.split("compile path")[0]   # 0: left out
    assert reader("startup_caller_share", cell).read(None) == 45.0
    assert reader("startup_compile_path_s", cell).read(None) == 8.0
    assert reader("startup_cache_misses", cell).read(None) == 3.0
    assert reader("startup_weights_s", cell).read(None) == 7.5
    assert capsys.readouterr().out == ""      # one line, one reader


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_module_reads_nothing(monkeypatch, name):
    # ``from neuronx_distributed_tpu.obs import startup`` on a commit older
    # than the module
    import neuronx_distributed_tpu.obs as obs

    monkeypatch.delattr(obs, "startup", raising=False)
    monkeypatch.setitem(sys.modules, "neuronx_distributed_tpu.obs.startup",
                        None)
    assert startup_account.snapshot() is None
    assert reader(name, CELLS[0]).read(None) is None


def test_an_account_that_never_was_ready_reads_nothing(monkeypatch):
    from neuronx_distributed_tpu.obs import startup

    monkeypatch.setattr(startup, "_ACCOUNT",
                        startup.StartupAccount(origin=0.0))
    assert startup_account.snapshot() is None
    startup.account().ready("engine")
    snap = startup_account.snapshot()
    assert snap["label"] == "engine" and snap["startup/ready_s"] > 0
    assert set(SNAP) == set(snap)             # the names the readers lean on


def test_the_entries_are_the_five_and_every_cell_reports_them():
    cells = [w["name"] for w in manifest.Cell(CELLS[0]).manifest["workloads"]]
    entries = {m["name"]: m for m in manifest.Cell(
        CELLS[0]).manifest["per_layer"] if m["moves"] == "setup_s"}
    assert tuple(entries) == READERS
    for name, m in entries.items():
        assert m["workloads"] == cells and m["source"] == "program_counter"
        mod = reader(name, CELLS[1])
        assert (mod.LAYER, mod.UNIT, mod.SOURCE) \
            == (m["layer"], m["unit"], m["source"])
    for cell in cells:
        assert set(READERS) <= {m["name"]
                                for m in manifest.Cell(cell).per_layer}
