"""Share of the window's wall the controller spends in syscall-service
rounds: the window's ``syscall_service_s`` over its wall."""

UNIT = "%"


def read(raw: dict):
    wall = raw.get("window_wall_s")
    if not wall or "window_syscall_service_s" not in raw:
        return None
    return 100.0 * raw["window_syscall_service_s"] / wall
