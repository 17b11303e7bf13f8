"""The readers of the fused driver's run journal (``lib/run_journal.py``:
one row a ``TpuEngine.run``, found in ``shadow_tpu.obs.clock.journal``) on
hand-made rows — which rows are the window's, every reader's arithmetic —
and the two readers of the hybrid's ``sync_stats`` counters."""

from collections import namedtuple

import pytest

import run
from lib import run_journal

PHASES = ("state_build", "dispatch", "device_wait", "collect", "fault_swap",
          "run")
NOTES = ("mode", "rounds", "lane_iters", "segments", "state_reused",
         "log_capacity", "lanes", "pops_per_iter", "stream_pops", "flows",
         "loop_pop_slots", "loop_tier_pop_slots", "loop_active_lanes",
         "loop_iters_no_send", "loop_exchange_passes", "loop_round_iters_max",
         "loop_round_iters_p50", "loop_round_iters_p95")
Row = namedtuple("Row", ("turn", "t_start", "t_end") + PHASES + NOTES
                 + ("owner",))


def row(owner, turn, iters=100, rounds=10, **vals):
    base = {f: 0 for f in Row._fields}
    base.update(
        owner=owner, turn=turn, t_start=float(turn), t_end=turn + 0.5,
        lane_iters=iters, rounds=rounds, lanes=50, pops_per_iter=2,
        state_build=0.001, dispatch=0.002, device_wait=0.1, collect=0.003,
        run=0.0005, loop_round_iters_max=17)
    base.update(vals)
    return Row(**base)


def raw_of(n=3, iters=100, rounds=10):
    return {"lane_iters": [iters] * n, "rounds": [rounds] * n,
            "call_wall_s": [0.1065] * n}


def journal(n=3, traced=True, **last):
    """The runner's engine (owner 4): a warm-up, ``n`` repeats, the traced
    one; then the check program's engine (owner 5), one run."""
    rows = [row(4, 0, state_build=0.9, dispatch=0.7)]
    rows += [row(4, 1 + i) for i in range(n - 1)]
    rows += [row(4, n, **last)]
    if traced:
        rows += [row(4, n + 1, dispatch=0.05)]
    return rows + [row(5, 0, iters=7, rounds=2)]


# -- which rows are the window's ---------------------------------------------------

def test_the_windows_rows_are_the_repeats_between_warm_up_and_trace():
    found = run_journal.match_window(journal(), raw_of())
    assert [r.turn for r in found.rows] == [1, 2, 3]
    assert {r.owner for r in found.rows} == {4}
    assert found.traced.turn == 4 and found.traced.dispatch == 0.05
    untraced = run_journal.match_window(journal(traced=False), raw_of())
    assert [r.turn for r in untraced.rows] == [1, 2, 3]
    assert untraced.traced is None


@pytest.mark.parametrize("why, rows, raw", [
    ("no rows", [], raw_of()),
    ("a repeat raised: one row more than the window counted",
     journal(4), raw_of(3)),
    ("a repeat's iterations are not the window's",
     journal(lane_iters=99), raw_of()),
    ("a repeat's rounds are not the window's",
     journal(rounds=9), raw_of()),
    ("the runner repeats nothing (the hybrid's raw)", journal(),
     {"sync_stats": {}}),
    ("only the check program's engine", [row(5, 0)], raw_of(1)),
])
def test_a_mismatch_gives_nothing(why, rows, raw):
    assert run_journal.match_window(rows, raw) is None, why


def test_a_tree_without_the_journal_gives_nothing(monkeypatch):
    from shadow_tpu.obs import clock

    monkeypatch.delattr(clock, "journal")
    assert run_journal.fused_journal() is None
    assert run_journal.window_runs(raw_of()) is None
    for name in ("fused_collect_ms", "pop_slot_fill", "round_iters_p95"):
        assert run.load_module("layer_metrics", name).read(raw_of()) is None


# -- the readers' arithmetic -----------------------------------------------------------

@pytest.fixture
def window(monkeypatch):
    """The journal of a traced run of a tiered fan-out program (no such
    program exists: every reader finds its counts)."""
    rows = journal(
        fault_swap=0.004, stream_pops=6, flows=5, loop_pop_slots=2_500,
        loop_tier_pop_slots=1_200, loop_active_lanes=2_000,
        loop_iters_no_send=25, loop_exchange_passes=120,
        loop_round_iters_p95=15)
    monkeypatch.setattr(run_journal, "fused_journal", lambda: rows)
    return raw_of()


@pytest.mark.parametrize("name, unit, want", [
    # means over the window's three repeats (the warm-up's 0.9 s and the
    # traced repeat's 0.05 s are outside)
    ("fused_state_build_ms", "ms", 1.0),
    ("fused_dispatch_ms", "ms", 2.0),
    ("fused_collect_ms", "ms", 3.0),
    ("fault_swap_ms", "ms", 4.0 / 3),
    # counts of the window's last repeat
    ("pop_slot_fill", "%", 100.0 * 2_500 / (100 * 2 * 50)),
    ("tier_pop_slot_fill", "%", 100.0 * 1_200 / (100 * 6 * 2 * 5)),
    ("active_lane_share", "%", 100.0 * 2_000 / (100 * 50)),
    ("iters_no_send_share", "%", 25.0),
    ("round_iters_p95", "iters/window", 15.0),
    ("exchange_passes_per_iter", "passes/iter", 1.2),
])
def test_a_readers_arithmetic(window, name, unit, want):
    mod = run.load_module("layer_metrics", name)
    assert mod.UNIT == unit
    assert mod.read(window) == pytest.approx(want, rel=1e-12)


def test_a_program_without_the_ledger_or_the_phase_reads_nothing(monkeypatch):
    rows = [row(4, 0), row(4, 1, loop_round_iters_max=0)]
    monkeypatch.setattr(run_journal, "fused_journal", lambda: rows)
    raw = raw_of(1)
    read = lambda name: run.load_module("layer_metrics", name).read(raw)
    assert read("fused_collect_ms") == pytest.approx(3.0)
    assert read("pop_slot_fill") is None  # no ledger in this program
    assert read("tier_pop_slot_fill") is None
    # a ledger, no tier: nothing offered
    rows[1] = row(4, 1)
    assert read("pop_slot_fill") == 0.0
    assert read("tier_pop_slot_fill") is None
    # an engine with no fault schedule has no such column
    Plain = namedtuple("Plain", [f for f in Row._fields if f != "fault_swap"])
    rows[:] = [Plain(**{f: getattr(r, f) for f in Plain._fields})
               for r in rows]
    assert read("fault_swap_ms") is None
    assert read("fused_dispatch_ms") == pytest.approx(2.0)


def test_the_phases_line_is_said_once(window, monkeypatch, capsys):
    monkeypatch.setattr(run_journal, "_said", False)
    run_journal.window_runs(window)
    run_journal.window_runs(window)
    out = capsys.readouterr().out.splitlines()
    assert len(out) == 1 and out[0].startswith("[bench journal]")
    # 1 + 2 + 100 + 3 + 0.5 ms of phases, 4 / 3 of swaps, against 106.5
    assert "sum 107.833 against the call's wall 106.500" in out[0]


# -- the hybrid's two counters ---------------------------------------------------------

def test_the_hybrids_copy_and_head_read_counters():
    raw = {"sync_stats": {"device_turns": 1349, "h2d_copies": 1367,
                          "egress_head_reads": 1345, "egress_reads": 2}}
    copies = run.load_module("layer_metrics", "h2d_copies_per_turn")
    heads = run.load_module("layer_metrics", "egress_head_read_share")
    assert (copies.UNIT, heads.UNIT) == ("count", "%")
    assert copies.read(raw) == pytest.approx(1367 / 1349)
    assert heads.read(raw) == pytest.approx(100.0 * 1345 / 1347)
    # a program without the counters, a runner without ``sync_stats``
    for empty in ({}, {"sync_stats": {"device_turns": 5}}, raw_of()):
        assert copies.read(empty) is None and heads.read(empty) is None
    assert heads.read({"sync_stats": {"egress_head_reads": 0,
                                      "egress_reads": 0}}) is None
