"""benchmarks/compare.py — the BENCH_*.json regression gate (ISSUE-10).

Pure-host tests: every case feeds --records/--fresh fixtures through
``main(argv)`` directly, so no benchmark is actually re-run and nothing
touches jax. The gate's contract: exit 0 when every shared timing key is
within threshold, exit 1 when any regresses, only ``*_ms``/``*_us``-style
keys are gated (counts, ratios, metadata never are), and malformed or
runner-less sections are skipped rather than failed.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks import compare  # noqa: E402 — needs the repo root on path


def _write(path, doc):
    path.write_text(json.dumps(doc))
    return str(path)


COMMITTED = {
    "what": "hierarchy",
    "arch": "paper-cnn",
    "k16_flat_comm_ms": 100.0,
    "k16_flat_global_syncs": 12,
    "k16_gp4_over_gp1": 0.93,
    "e2e_tau4_flat_ms_per_round": 1000.0,
}


def test_identical_fresh_run_passes(tmp_path, capsys):
    rec = _write(tmp_path / "BENCH_x.json", COMMITTED)
    fresh = _write(tmp_path / "fresh.json", COMMITTED)
    assert compare.main(["--records", rec, "--fresh", fresh]) == 0
    assert "[ ok ]" in capsys.readouterr().out


def test_inflated_timing_fails_and_names_the_key(tmp_path, capsys):
    rec = _write(tmp_path / "BENCH_x.json", COMMITTED)
    bad = dict(COMMITTED, k16_flat_comm_ms=200.0)
    fresh = _write(tmp_path / "fresh.json", bad)
    assert compare.main(["--records", rec, "--fresh", fresh]) == 1
    out = capsys.readouterr().out
    assert "REGRESSED" in out and "k16_flat_comm_ms" in out
    assert "[FAIL]" in out


def test_non_timing_keys_are_never_gated(tmp_path):
    rec = _write(tmp_path / "BENCH_x.json", COMMITTED)
    # syncs count and the gp ratio blow up 100x; timing keys stay put
    bad = dict(COMMITTED, k16_flat_global_syncs=1200, k16_gp4_over_gp1=93.0)
    fresh = _write(tmp_path / "fresh.json", bad)
    assert compare.main(["--records", rec, "--fresh", fresh]) == 0


def test_threshold_is_respected(tmp_path):
    rec = _write(tmp_path / "BENCH_x.json", COMMITTED)
    fresh = _write(tmp_path / "fresh.json",
                   dict(COMMITTED, k16_flat_comm_ms=180.0))
    assert compare.main(["--records", rec, "--fresh", fresh]) == 1
    assert compare.main(["--records", rec, "--fresh", fresh,
                         "--threshold", "2.0"]) == 0


def test_wrapper_document_csv_and_nested_sections(tmp_path, capsys):
    doc = {
        "date": "2026-08-08",
        "sections": {
            "kernels": [
                {"name": "elastic_k4", "us_per_call": 10.0},
                {"name": "elastic_k8", "us_per_call": 20.0},
            ],
            "scenarios": {"what": "scenarios",
                          "arms": {"clean": {"k4_ms_per_round": 5.0}}},
        },
    }
    rec = _write(tmp_path / "BENCH_w.json", doc)
    # nested arms regress through the dot-joined flattening
    fresh = _write(tmp_path / "fresh.json",
                   {"what": "scenarios",
                    "arms": {"clean": {"k4_ms_per_round": 50.0}}})
    assert compare.main(["--records", rec, "--fresh", fresh]) == 1
    out = capsys.readouterr().out
    assert "arms.clean.k4_ms_per_round" in out
    # with --fresh, csv sections are not re-run — they're skipped silently
    assert "elastic_k4" not in out


def test_malformed_and_runnerless_records_are_skipped(tmp_path, capsys):
    broken = tmp_path / "BENCH_broken.json"
    broken.write_text("{not json")
    unknown = _write(tmp_path / "BENCH_unknown.json",
                     {"what": "no_such_bench", "x_ms": 1.0})
    # no --fresh: the unknown section has no registered runner, so it is
    # skipped (and nothing else is runnable, so no bench executes)
    assert compare.main(["--records", str(broken), unknown]) == 0
    out = capsys.readouterr().out
    assert "not valid JSON" in out
    assert "no runner registered" in out
    assert out.count("[skip]") == 2


def test_no_records_is_a_pass(tmp_path, capsys):
    assert compare.main(["--records"]) == 0
    assert "no committed" in capsys.readouterr().out


def test_committed_bench_files_parse_into_sections():
    # the records actually committed at the repo root must all be
    # readable by the gate and expose at least one gated timing key
    import glob
    import os

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
    assert paths, "no committed BENCH_*.json records"
    for path in paths:
        with open(path) as f:
            doc = json.load(f)
        sections = list(compare.committed_sections(doc))
        assert sections, path
        timed = [k for _, _, rec in sections for k in rec
                 if k.endswith(compare.TIMING_SUFFIXES)]
        assert timed, path


def test_benchmark_harness_exits_nonzero_when_a_section_raises(
        monkeypatch, capsys):
    import pytest

    from benchmarks import kernels_bench, run

    def broken():
        raise RuntimeError("section blew up")

    monkeypatch.setattr(kernels_bench, "bench", broken)
    monkeypatch.setattr("repro.launch.compile_cache.enable_compile_cache",
                        lambda: "")
    with pytest.raises(SystemExit) as exc:
        run.main(["--what", "kernels"])
    assert exc.value.code not in (0, None)
    assert "kernels,0,ERROR:RuntimeError:section blew up" in (
        capsys.readouterr().out)
