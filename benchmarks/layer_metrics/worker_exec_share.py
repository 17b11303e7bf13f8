"""Share of the syscall-service rounds' wall in which the slowest worker
was executing its hosts: sum of each round's largest worker execution wall
(a worker times its own round and replies with it) over the rounds' ship +
collect legs, inside the window's turns.  The rest is pickling, pipes and
wake-ups: a LOW reading says the syscall plane's wall is pipes, not
syscalls."""

UNIT = "%"


def read(raw: dict):
    from lib.turn_spans import phase_seconds, window_rows

    rows = window_rows(raw)
    if not rows:
        return None
    service = phase_seconds(rows, ("service_ship", "service_collect"))
    if not service:
        return None
    return 100.0 * sum(r.worker_exec_max_s for r in rows) / service
