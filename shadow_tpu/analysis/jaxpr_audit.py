"""Pass 2: jaxpr parity auditor for the lane/stream/hybrid kernels.

Traces the real device programs with ``jax.make_jaxpr`` — no device run,
no compile — and audits every equation (recursing into while/cond/scan/
pjit sub-jaxprs) for the hazards that break cross-backend bit parity:

- SL201 float64 avals (x64 mode exists for int64 sim time; a traced f64
  is almost always a leaked Python float),
- SL202 weak-type float scalars (backend-dependent promotion),
- SL203 ``lax.sort`` with ``is_stable=False``,
- SL204 host callbacks inside the jitted region,
- SL205 non-associative float reductions (reduce_sum/cumsum/dot/psum on
  inexact dtypes) off the fixed-order reduction seam.  The lane kernel's
  one sanctioned float op — the one-hot histogram matmul, exact in f32
  for counts < 2**24 (``lanes._merge_append``) — carries a justified
  entry in the baseline file rather than an invisible in-code exemption.

Findings use ``kernel:<name>/<entry>`` as their path and a primitive/
dtype/shape signature as the fingerprint detail, so they are stable
across retraces and unrelated kernel edits.

The representative configs in :data:`KERNELS` are chosen to cover the
distinct program shapes: the pure-lane tier (phold), the passive packet
tier with loss (tgen UDP), and the compacted stream-TCP tier.  Adding a
new kernel family to the repo should add an entry here — the CLI audits
all of them by default.
"""

from __future__ import annotations

from typing import Iterable, Optional

from .findings import Finding

# reductions whose float result depends on XLA's accumulation order.
# max/min/argmax are order-independent; integer ops are exact; and
# reduce_precision is elementwise rounding (no accumulation at all).
_NONASSOC_REDUCE_PRIMS = {
    "reduce_sum", "reduce_prod", "cumsum", "cumprod", "cumlogsumexp",
    "dot_general", "add_any", "psum", "reduce_window_sum",
}

_CALLBACK_PRIMS = {"io_callback", "pure_callback", "debug_callback"}

KERNELS = {
    # pure lane tier: self-loop phold ring, the PDES classic
    "phold": """
general: {stop_time: 200ms, seed: 1}
experimental: {network_backend: tpu}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "1 Gbit" host_bandwidth_down "1 Gbit" ]
        edge [ source 0 target 0 latency "5 ms" ]
      ]
hosts:
  p: {count: 8, network_node_id: 0, processes: [{path: phold, args: [--messages, "3"]}]}
""",
    # passive packet tier with loss sampling (counter RNG on-device)
    "tgen_udp": """
general: {stop_time: 100ms, seed: 3}
experimental: {network_backend: tpu}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "10 Mbit" host_bandwidth_down "10 Mbit" ]
        node [ id 1 host_bandwidth_up "10 Mbit" host_bandwidth_down "10 Mbit" ]
        edge [ source 0 target 1 latency "10 ms" packet_loss 0.2 ]
      ]
hosts:
  tx: {network_node_id: 0, processes: [{path: tgen-client, args: [--server, rx, --interval, 5ms, --size, "600"]}]}
  rx: {network_node_id: 1, processes: [{path: tgen-server}]}
""",
    # compacted stream-TCP tier (handshake/Reno/RTO law)
    "stream_tcp": """
general: {stop_time: 500ms, seed: 1}
experimental: {network_backend: tpu, tpu_lane_queue_capacity: 64}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "20 Mbit" host_bandwidth_down "20 Mbit" ]
        node [ id 1 host_bandwidth_up "20 Mbit" host_bandwidth_down "20 Mbit" ]
        edge [ source 0 target 0 latency "1 ms" ]
        edge [ source 0 target 1 latency "40 ms" packet_loss 0.02 ]
        edge [ source 1 target 1 latency "1 ms" ]
      ]
hosts:
  client: {count: 2, network_node_id: 0, processes: [{path: stream-client, args: [--server, server, --size, 64KiB]}]}
  server: {network_node_id: 1, processes: [{path: stream-server}]}
""",
}


def _aval_sig(v) -> str:
    aval = getattr(v, "aval", None)
    if aval is None or not hasattr(aval, "dtype"):
        return "?"
    weak = "w" if getattr(aval, "weak_type", False) else ""
    shape = "x".join(str(d) for d in getattr(aval, "shape", ()))
    return f"{aval.dtype.name}{weak}[{shape}]"


def _is_float(v) -> bool:
    aval = getattr(v, "aval", None)
    dtype = getattr(aval, "dtype", None)
    return dtype is not None and dtype.kind == "f"


def audit_jaxpr(closed_jaxpr, label: str) -> list[Finding]:
    """Audit one (closed) jaxpr; ``label`` becomes the finding path."""
    findings: dict[str, Finding] = {}
    # number repeated identical signatures, mirroring the AST pass: a
    # SECOND equation with the same primitive/dtype/shape signature is a
    # distinct hazard needing its own baseline entry, not a free rider
    sig_counts: dict[tuple[str, str], int] = {}

    def emit(rule: str, message: str, detail: str) -> None:
        key = (rule, detail)
        n = sig_counts.get(key, 0)
        sig_counts[key] = n + 1
        f = Finding(
            rule=rule, path=label, line=0, col=0,
            message=message, detail=detail, occurrence=n,
        )
        findings[f.fingerprint] = f

    def walk(jaxpr) -> None:
        jaxpr = getattr(jaxpr, "jaxpr", jaxpr)  # ClosedJaxpr -> Jaxpr
        for eqn in jaxpr.eqns:
            prim = eqn.primitive.name
            in_sigs = ",".join(_aval_sig(v) for v in eqn.invars)
            out_sigs = ",".join(_aval_sig(v) for v in eqn.outvars)
            sig = f"{prim}({in_sigs})->{out_sigs}"
            if prim == "sort":
                sig += (
                    f"{{num_keys={eqn.params.get('num_keys')},"
                    f"dim={eqn.params.get('dimension')}}}"
                )

            for v in list(eqn.outvars) + list(eqn.invars):
                aval = getattr(v, "aval", None)
                dtype = getattr(aval, "dtype", None)
                if dtype is None:
                    continue
                if dtype.name == "float64":
                    emit(
                        "SL201",
                        f"float64 aval in `{prim}` — leaked Python float? "
                        "pin an explicit narrow dtype",
                        sig,
                    )
                    break
            for v in list(eqn.outvars) + list(eqn.invars):
                aval = getattr(v, "aval", None)
                if (
                    _is_float(v)
                    and getattr(aval, "weak_type", False)
                ):
                    emit(
                        "SL202",
                        f"weak-type float in `{prim}` promotes "
                        "backend-dependently — pin the dtype at the literal",
                        sig,
                    )
                    break

            if prim == "sort" and not eqn.params.get("is_stable", True):
                emit(
                    "SL203",
                    "unstable lax.sort — equal keys may reorder across "
                    "backends; pass is_stable=True or a total key",
                    sig,
                )

            if prim in _CALLBACK_PRIMS or "callback" in prim:
                emit(
                    "SL204",
                    f"host callback `{prim}` inside the jitted kernel — "
                    "hoist to a window boundary",
                    sig,
                )

            if prim in _NONASSOC_REDUCE_PRIMS and any(
                _is_float(v) for v in eqn.invars
            ):
                emit(
                    "SL205",
                    f"float `{prim}` — accumulation order changes the "
                    "bits unless the values are exactly representable; "
                    "keep it integral or baseline with a proof",
                    sig,
                )

            for sub in _sub_jaxprs(eqn.params):
                walk(sub)

    walk(closed_jaxpr)
    return sorted(
        findings.values(), key=lambda f: (f.rule, f.detail)
    )


def _sub_jaxprs(params: dict):
    """Yield every Jaxpr/ClosedJaxpr nested in an eqn's params."""
    for v in params.values():
        for item in v if isinstance(v, (list, tuple)) else (v,):
            if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                yield item


def trace_kernel(name: str, yaml_src: str) -> list[tuple[str, object]]:
    """Build the TPU engine for a config and trace its device entry
    points.  Returns ``[(label, closed_jaxpr), ...]``."""
    import jax

    from ..backend import lanes
    from ..backend.tpu_engine import TpuEngine
    from ..config.options import ConfigOptions

    cfg = ConfigOptions.from_yaml(yaml_src)
    eng = TpuEngine(cfg)
    state = eng.initial_state()
    round_fn = lanes._build_round(eng.params, eng.tables)
    full_fn = lanes._build_full_run(eng.params, eng.tables)
    return [
        (f"kernel:{name}/round", jax.make_jaxpr(round_fn)(state)),
        (f"kernel:{name}/full_run", jax.make_jaxpr(full_fn)(state)),
    ]


def audit_kernels(names: Optional[Iterable[str]] = None) -> list[Finding]:
    """Trace and audit the representative kernels (all by default)."""
    findings: list[Finding] = []
    for name in names if names is not None else KERNELS:
        yaml_src = KERNELS[name]
        for label, jaxpr in trace_kernel(name, yaml_src):
            findings.extend(audit_jaxpr(jaxpr, label))
    return findings
