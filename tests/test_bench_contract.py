"""The bench failure contract: incremental cumulative emission, a failed
section recorded instead of destroying the run, and the driver-facing
record keys. A regression here silently reverts to the all-or-nothing
bench that loses every number to one late failure.
"""

import json

import bench


class TestEmitter:
    def test_every_line_is_the_full_cumulative_record(self, capsys):
        e = bench._Emitter()
        e.update(value=1.0, mfu=0.3)
        e.update(vs_baseline=0.99)
        out = capsys.readouterr().out.strip().splitlines()
        assert len(out) == 2
        first, last = (json.loads(line) for line in out)
        # Driver contract keys present from the very first line.
        for k in ("metric", "value", "unit", "vs_baseline"):
            assert k in first
        # The LAST line carries everything measured so far.
        assert last["value"] == 1.0 and last["mfu"] == 0.3
        assert last["vs_baseline"] == 0.99

    def test_last_line_survives_later_failure(self, capsys):
        e = bench._Emitter()
        e.update(value=2724.07, mfu=0.339)
        # a later section failing emits nothing — the last complete line
        # still holds the headline row.
        out = capsys.readouterr().out.strip().splitlines()
        rec = json.loads(out[-1])
        assert rec["value"] == 2724.07


class TestRunSection:
    def test_result_passes_through(self):
        errors = []
        assert bench._run_section("s", lambda: "ok", errors) == "ok"
        assert not errors

    def test_failure_is_recorded_once_and_not_retried(self):
        calls = []

        def failing():
            calls.append(1)
            raise RuntimeError("UNAVAILABLE: socket closed")

        errors = []
        assert bench._run_section("s", failing, errors) is None
        assert len(calls) == 1
        assert len(errors) == 1 and "socket closed" in errors[0]

    def test_exit_code_is_zero_only_for_a_clean_run_with_a_headline(self):
        headline = (0.047, 0.01)
        assert bench._exit_code(headline, []) == 0
        assert bench._exit_code(headline, ["bert: ValueError: x"]) == 1
        assert bench._exit_code(None, []) == 1
