"""The manager's after-commit work per save wherever it runs: thread-seconds
of index and retention (`manager:index`, retention nested in it), history /
ledger / SLOs (`telemetry:report` with `kind="step"`; the commit thread's
report emission, another `kind`, is not it) and the tuner's decision and
install (`manager:tune`), on any thread. `post_commit_s` beside it counts the
caller's thread alone: since PR 39 an `async_save`'s work runs on the take's
commit thread before `done()` turns true, so that one reads the install and
this one what the work still costs. On a library that runs it all in
`wait()` (a parent of PR 39) the two read the same."""

from typing import Any, Dict, Optional

from stage_table import ops, span_names


def read(run: Dict[str, Any]) -> Optional[float]:
    window = ops(run)
    manager = span_names("SPAN_MANAGER_INDEX", "SPAN_MANAGER_TUNE")
    report = span_names("SPAN_TELEMETRY_REPORT")
    if window is None or not manager:
        return None
    values = []
    for op in window:
        spans = [e for e in op["events"] if e["name"] in manager
                 or (e["name"] in report and e["args"].get("kind") == "step")]
        if spans:
            values.append(sum(e["dur"] for e in spans) / 1e6)
    return sum(values) / len(values) if values else None
