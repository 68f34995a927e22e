"""Each cell's traffic at a tiny size, through the rest of a run, with
the plain reference in the program's place: sound, with a guarantee
left out, and with the timed path broken underneath."""

from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys

import pytest

from perfbench import manifest, run

from . import tiny

CELLS = [w["name"] for w in manifest.load_manifest()["workloads"]]
VERIFY_CELLS = [c for c in CELLS if manifest.load_cell(c).config["kind"] == "verify"]
ROOT_CELLS = [c for c in CELLS if manifest.load_cell(c).config["kind"] == "root"]


def compared(result: dict) -> dict:
    return {c["name"]: c for c in result["compared"]}


@pytest.mark.parametrize("name", CELLS)
def test_a_sound_run_is_correct_and_reports_the_cells_end_to_end_metrics(name):
    result = tiny.run_tiny(name)
    assert result["correct"], result["compared"]
    assert result["failed"] == 0 and result["attempted"] > 0
    want = {m["name"] for m in manifest.load_cell(name).end_to_end}
    assert set(result["metrics"]) == want
    assert all(v["value"] > 0 for v in result["metrics"].values())
    assert list(result)[-1] == "compared"
    json.dumps(result)


@pytest.mark.parametrize("name", CELLS)
def test_a_traced_run_without_a_chip_reports_only_what_it_could_read(name):
    result = tiny.run_tiny(name, traced=True)
    listed = {m["name"] for m in manifest.load_cell(name).per_layer}
    assert set(result["metrics"]) <= listed
    assert not any("hbm_share" in k for k in result["metrics"])
    assert "busy_s" not in result["device"]


@pytest.mark.parametrize("name", VERIFY_CELLS)
@pytest.mark.parametrize("control", ["no_blinding", "no_subgroup_check"])
def test_a_verify_control_comes_out_not_correct(name, control):
    result = tiny.run_tiny(name, control=control)
    assert not result["correct"]
    assert compared(result)["verdict_mismatches"]["value"] >= 1


@pytest.mark.parametrize("name", ROOT_CELLS)
def test_the_root_control_comes_out_not_correct(name):
    result = tiny.run_tiny(name, control="stale_repeat")
    assert not result["correct"]
    assert compared(result)["root_mismatches"]["value"] >= 1


@pytest.mark.parametrize("name", VERIFY_CELLS)
@pytest.mark.parametrize("broken", [tiny.AlwaysTrue, tiny.HalfLeftOut, tiny.LastJobDropped,
                                    tiny.FirstJobDropped])
@pytest.mark.parametrize("seed", [3, 2**31 + 11, 2**32 + 5])
def test_a_broken_verifier_is_caught_on_every_seed(name, broken, seed):
    result = tiny.run_tiny(name, system_factory=broken, seed=seed)
    assert not result["correct"]
    assert not compared(result)["verdict_mismatches"]["holds"]


@pytest.mark.parametrize("broken,seed", [(tiny.HalfLeftOut, 7), (tiny.HalfLeftOut, 2**31 + 12),
                                         (tiny.HalfLeftOut, 2**32 + 6), (tiny.LastJobDropped, 2**33 + 1)])
def test_half_a_call_left_out_is_caught_at_the_cells_own_call_size(broken, seed):
    """131 sets a call, jobs of 66 and 65 as the pool makes them."""
    result = tiny.run_tiny(VERIFY_CELLS[0], system_factory=broken, seed=seed, full_calls=True)
    assert not result["correct"]
    assert compared(result)["verdict_mismatches"]["value"] >= 2
    assert compared(result)["faulty_last_job_calls"]["holds"]


@pytest.mark.parametrize("n,per_job,sizes", [(131, 128, [66, 65]), (5, 3, [3, 2]), (128, 128, [128]),
                                             (300, 128, [100, 100, 100])])
def test_job_spans_are_the_references_chunks(n, per_job, sizes):
    spans = tiny.verify_kind.job_spans(n, per_job)
    assert [len(s) for s in spans] == sizes
    assert [k for s in spans for k in s] == list(range(n))


@pytest.mark.parametrize("name", ROOT_CELLS)
@pytest.mark.parametrize("broken", [tiny.StateUnchanged, tiny.RootAltered, tiny.HalfDirtyLeftOut])
def test_a_broken_collector_is_caught(name, broken):
    result = tiny.run_tiny(name, system_factory=broken)
    assert not result["correct"]
    assert not compared(result)["root_mismatches"]["holds"]


@pytest.mark.parametrize("name", VERIFY_CELLS[:1])
def test_a_run_with_no_faulty_call_proves_nothing(name):
    cell = tiny.tiny_cell(name)
    cell.traffic["faults"] = {}
    import asyncio

    from perfbench.entries import reference

    result = asyncio.run(run.run_cell(cell, 5, 0.2, False, lambda: reference.boot(cell.config), dict(tiny.HOST)))
    assert not result["correct"]
    assert not compared(result)["faulty_first_job_calls"]["holds"]
    assert not compared(result)["faulty_last_job_calls"]["holds"]


@pytest.mark.parametrize("control,rc,seeds_arg", [(None, 0, "9,4294967305"), ("no_subgroup_check", 1, "9")])
def test_many_seeds_in_one_process_read_correct_for_each(control, rc, seeds_arg, capsys):
    from perfbench import seeds

    argv = ["--workloads", VERIFY_CELLS[0], "--seeds", seeds_arg, "--seconds", "0.2",
            "--entry", "reference"] + (["--control", control] if control else [])
    assert seeds.main(argv) == rc
    lines = [json.loads(line) for line in capsys.readouterr().out.splitlines()]
    assert [line["seed"] for line in lines] == [int(s) for s in seeds_arg.split(",")]
    assert [line["correct"] for line in lines] == [rc == 0] * len(lines)


def test_without_a_chip_the_command_fails_and_prints_no_result(capsys):
    rc = run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"])
    assert rc == run.EXIT_NO_CHIP
    assert capsys.readouterr().out == ""


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        run.load_peaks("TPU v9 imaginary")
    assert run.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_in_a_directory_with_only_the_benchmark_the_command_fails(tmp_path):
    m = manifest.load_manifest()
    shutil.copy(manifest.MANIFEST_PATH, tmp_path / "BENCHMARK.json")
    for p in m["paths"]:
        shutil.copytree(os.path.join(manifest.ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    res = subprocess.run(
        [sys.executable, *m["command"][1:], "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120,
    )
    assert res.returncode != 0 and res.stdout == ""
    # and past the look for a chip, the program itself is what is missing
    probe = "import asyncio, sys; sys.path.insert(0, '.'); from perfbench.entries import node; asyncio.run(node.boot({}))"
    res = subprocess.run([sys.executable, "-c", probe], cwd=tmp_path, env=env, capture_output=True,
                         text=True, timeout=120)
    assert res.returncode != 0 and "lodestar_tpu" in res.stderr
