"""A whole run with the timed path broken underneath comes out not correct,
once for each fault a cell of this benchmark can have (one chip: there is no
exchange between chips to leave out)."""
import dataclasses

import pytest

from bench.tests._cpu_run import small_run


def _alter_answers(monkeypatch):
    from repro.core.aqp_query import QueryEngine

    inner = QueryEngine.run_compiled

    def run_compiled(self, *a, **kw):
        return [dataclasses.replace(r, estimate=r.estimate * 1.001)
                for r in inner(self, *a, **kw)]
    monkeypatch.setattr(QueryEngine, "run_compiled", run_compiled)


def _half_sample(monkeypatch):
    from repro.core import aqp_query

    inner = aqp_query._make_plan

    def make_plan(syn):
        half = dataclasses.replace(syn, x=syn.x[: syn.x.shape[0] // 2])
        return inner(half)
    monkeypatch.setattr(aqp_query, "_make_plan", make_plan)


def _inserts_dropped(monkeypatch):
    from repro.data import TelemetryStore

    inner = TelemetryStore.add_batch
    calls = []

    def add_batch(self, stats):
        calls.append(1)
        if len(calls) == 1:          # the first ingest, in set-up
            return inner(self, stats)
    monkeypatch.setattr(TelemetryStore, "add_batch", add_batch)


FAULTS = {"sound": None, "answer_altered": _alter_answers,
          "half_the_sample": _half_sample, "state_unchanged": _inserts_dropped}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_is_refused(fault, monkeypatch):
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    result, _extra = small_run("tpch.refresh-quiesced", seconds=2.0)
    assert result["correct"] is (fault == "sound"), result["checks"]
