"""The comparisons that decide ``correct`` (copied from chip_smoke.py's
``assert_counters_equal`` / ``assert_logs_equal`` / ``host_outputs``, made
to count differences instead of raising, so that every number compared can
be printed beside its limit).  Every comparison is exact: the limit is 0."""

from __future__ import annotations

from pathlib import Path

#: counters only one backend keeps (its own bookkeeping, not a simulated
#: statistic): the lane program's iteration / launch counts, the oracle's
#: sender-side byte total.
BACKEND_ONLY = frozenset(
    {"lane_iters", "lane_delivered", "lane_sends", "tgen_sent_bytes"}
)

#: the counters both sides of a hybrid run keep (chip_smoke.py phase c)
HYBRID_COUNTERS = ("udp_tx_bytes", "udp_rx_bytes", "managed_exit_clean",
                   "managed_tcp_rx_bytes", "tgen_recv_bytes")


class Comparison:
    """A list of ``(what, differing, limit)`` rows; ``ok`` when every
    ``differing`` is within its limit.  ``notes`` carry the first
    difference of a failed row, for the reader."""

    def __init__(self) -> None:
        self.rows: list[tuple[str, int, int]] = []
        self.notes: list[str] = []

    def add(self, what: str, differing: int, limit: int = 0,
            note: str = "") -> None:
        self.rows.append((what, int(differing), int(limit)))
        if differing > limit and note:
            self.notes.append(f"{what}: {note}")

    @property
    def ok(self) -> bool:
        return all(d <= lim for _w, d, lim in self.rows)

    @property
    def failures(self) -> int:
        """How many of the numbers compared are beyond their limit."""
        return sum(1 for _w, d, lim in self.rows if d > lim)

    def lines(self) -> list[str]:
        out = [f"compare {w}: differing={d} limit={lim}"
               for w, d, lim in self.rows]
        return out + [f"compare note: {n}" for n in self.notes]


def counters_diff(a: dict, b: dict, keys=None, ignore=frozenset()) -> dict:
    """``{key: (a, b)}`` for every key (of ``keys``, else of either side,
    less ``ignore``) on which the two counter dicts differ."""
    if keys is None:
        keys = (set(a) | set(b)) - set(ignore)
    return {k: (a.get(k), b.get(k)) for k in sorted(keys)
            if a.get(k) != b.get(k)}


def compare_counters(cmp: Comparison, what: str, a: dict, b: dict,
                     keys=None, ignore=frozenset()) -> None:
    diff = counters_diff(a, b, keys, ignore)
    cmp.add(f"{what} counters", len(diff), 0, str(dict(list(diff.items())[:5])))


def compare_errors(cmp: Comparison, what: str, a: list, b: list) -> int:
    """Process errors as sorted lists; returns how many are on one side
    only (at least 1 when the lists differ at all)."""
    ea, eb = sorted(a), sorted(b)
    bad = len(set(ea) ^ set(eb)) or int(ea != eb)
    cmp.add(f"{what} process errors ({len(ea)} / {len(eb)})", bad, 0,
            f"{ea[:3]} vs {eb[:3]}")
    return bad


def compare_logs(cmp: Comparison, what: str, a, b) -> int:
    """Event logs record for record (``SimResult.log_tuples``: the
    canonical order).  Counts the records that differ; an empty log is a
    difference too (nothing was compared).  Returns the record count."""
    la, lb = a.log_tuples(), b.log_tuples()
    if la == lb:
        cmp.add(f"{what} event-log records", 0 if la else 1, 0, "empty log")
        return len(la)
    n = min(len(la), len(lb))
    bad = sum(1 for i in range(n) if la[i] != lb[i]) + abs(len(la) - len(lb))
    i = next((i for i in range(n) if la[i] != lb[i]), n)
    cmp.add(f"{what} event-log records", bad, 0,
            f"first at record {i} of {len(la)}/{len(lb)}: "
            f"{la[i:i + 1]} vs {lb[i:i + 1]}")
    return len(la)


def host_outputs(data_dir: Path) -> dict:
    """Every managed process's stdout/stderr bytes, keyed by relative path."""
    out = {}
    hosts = Path(data_dir) / "hosts"
    for path in sorted(hosts.rglob("*")):
        if path.is_file() and path.suffix in (".stdout", ".stderr"):
            out[str(path.relative_to(data_dir))] = path.read_bytes()
    return out


def compare_outputs(cmp: Comparison, what: str, a: dict, b: dict) -> int:
    """Process output files byte for byte; a file only one side wrote
    differs.  No files at all is a difference (nothing was compared).
    Returns the number of files that differ."""
    names = set(a) | set(b)
    bad = sorted(k for k in names if a.get(k) != b.get(k))
    cmp.add(f"{what} process output files ({len(names)})",
            len(bad) if names else 1, 0,
            str(bad[:5]) if names else "no output files")
    return len(bad)


def compare_results(cmp: Comparison, what: str, got, ref, counter_keys=None,
                    out_got: dict | None = None,
                    out_ref: dict | None = None, log: bool = True) -> dict:
    """The whole comparison of one ``SimResult`` with the reference's:
    counters (``counter_keys``, else all but ``BACKEND_ONLY``), rounds,
    process errors, the event log (``log=False`` for a program that keeps
    none) and — where output was gathered — every process output file.
    Returns ``{records, bad_errors, bad_files}``."""
    compare_counters(cmp, what, got.counters, ref.counters,
                     keys=counter_keys, ignore=BACKEND_ONLY)
    cmp.add(f"{what} rounds ({got.rounds} / {ref.rounds})",
            int(got.rounds != ref.rounds))
    bad_errors = compare_errors(cmp, what, got.process_errors,
                                ref.process_errors)
    records = compare_logs(cmp, what, got, ref) if log else 0
    bad_files = 0
    if out_got is not None or out_ref is not None:
        bad_files = compare_outputs(cmp, what, out_got or {}, out_ref or {})
    return {"records": records, "bad_errors": bad_errors,
            "bad_files": bad_files}
