"""The served CLI (`python -m repro.launch.serve`): argument defaults and
validation, and tiny end-to-end runs of the admission loop — selectors, the
full-H override, the Pallas backend, coarse priority, restart from a
snapshot, and the `--metrics-out` export."""
import importlib.util
import json
import math
from pathlib import Path

import pytest

from repro import obs
from repro.checkpoint import CheckpointManager
from repro.launch import serve
from repro.obs import Tracer

TINY = ["--rows", "5000", "--capacity", "256", "--clients", "2",
        "--per-client", "8", "--stream-rows", "500"]
N_QUERIES = 2 * 8
MODEL_IDS = [0.0, 1.0, 2.0, 3.0]


def _run(*extra):
    return serve.run_aqp(serve.parse_args(TINY + list(extra)))


def _assert_answered(out):
    results = out["results"]
    assert len(results) == N_QUERIES
    assert all(math.isfinite(r.estimate) for r in results)
    assert sorted(r.group for r in out["group_by"]) == MODEL_IDS


@pytest.fixture
def obs_restored():
    """Give the test a fresh tracer and put obs back as it was afterwards."""
    prev_tracer = obs.set_tracer(Tracer())
    was = obs.enabled()
    yield
    if not was:
        obs.disable()
    obs.set_tracer(prev_tracer)


def test_defaults_are_aqp():
    args = serve.parse_args([])
    assert (args.rows, args.capacity) == (200_000, 2048)
    assert (args.selector, args.backend) == ("plugin", "jnp")
    assert not hasattr(args, "mode") and not hasattr(args, "arch")


@pytest.mark.parametrize("argv", [
    ["--snapshot-every", "0"],
    ["--metrics-every", "0"],
    ["--coarse-frac", "1.5"],
    ["--fullh-frac", "-0.1"],
    ["--restore"],                    # needs --snapshot-dir
], ids=["snapshot-every", "metrics-every", "coarse-frac", "fullh-frac",
        "restore-without-dir"])
def test_rejected_arguments(argv):
    with pytest.raises(SystemExit):
        serve.seed_aqp_store(serve.parse_args(TINY + argv))


@pytest.mark.parametrize("selector", ["plugin", "silverman", "lscv_h"])
def test_selector_answers_every_query(selector):
    out = _run("--selector", selector)
    _assert_answered(out)


def test_fullh_override_runs_on_qmc():
    out = _run("--fullh-frac", "0.25", "--kde-backend", "exact")
    _assert_answered(out)
    fullh = [r for r in out["results"] if r.query.selector == "lscv_H"]
    assert fullh
    assert all(r.path == "qmc" for r in fullh)


def test_pallas_backend_paths():
    out = _run("--backend", "pallas")
    _assert_answered(out)
    kde = [r for r in out["results"] if r.path.startswith(("range1d", "box"))]
    assert kde
    assert all(r.path.endswith(":pallas") for r in kde)


def test_coarse_priority_answers_every_query():
    out = _run("--coarse-frac", "0.5")
    _assert_answered(out)


def test_restore_resumes_from_last_snapshot(tmp_path):
    snap = str(tmp_path / "snap")
    args = serve.parse_args(TINY + ["--snapshot-dir", snap,
                                    "--snapshot-every", "1",
                                    "--stream-every-ms", "5"])
    seeded = serve.seed_aqp_store(args)
    store = seeded[0]
    serve.run_aqp(args, seeded=seeded)
    last = CheckpointManager(snap, async_save=False).latest_step()
    rows_seen = max(res.n_seen for res in store.columns.values())
    assert (rows_seen - 5000) % 500 == 0     # seed plus whole streamed batches

    restore = serve.parse_args(TINY + ["--snapshot-dir", snap, "--restore",
                                       "--stream-every-ms", "1e9"])
    restored = serve.seed_aqp_store(restore)
    r_store, n, step, telemetry = restored
    assert step == last
    assert n == rows_seen
    assert telemetry is None
    cat = r_store.stats()["categoricals"]["model_id"]
    assert cat["exact"] and cat["rows"] == n
    _assert_answered(serve.run_aqp(restore, seeded=restored))


def test_metrics_out_validates(tmp_path, obs_restored):
    path = Path(__file__).resolve().parents[1] / "scripts" / "validate_metrics.py"
    spec = importlib.util.spec_from_file_location("validate_metrics", path)
    validator = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(validator)

    out_file = tmp_path / "metrics.json"
    out = _run("--metrics-out", str(out_file), "--metrics-every", "0.3")
    _assert_answered(out)
    assert validator.validate_metrics(json.loads(out_file.read_text())) == []
