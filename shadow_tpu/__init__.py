"""shadow_tpu — a TPU-native discrete-event network simulator.

A ground-up rebuild of the capabilities of Shadow (reference:
``iiins0mn1a/shadow-gen``): a deterministic discrete-event simulation of an
IPv4 network (latency/loss graph, CoDel router queues, bandwidth token
buckets, simulated TCP/UDP transports) driving managed applications, with the
per-round packet-scheduling hot path implemented as a batched JAX/XLA program
— one lane per simulated host — behind a ``network-backend={cpu,tpu}`` switch
with bit-identical event ordering between backends.

Package layout:

- ``core``      time, events, queues, counter-based RNG (the determinism core)
- ``config``    typed-unit options, YAML config
- ``net``       graph/routing, packets, CoDel, token buckets, DNS
- ``transport`` sans-I/O UDP/TCP state machines
- ``engine``    controller/manager round loop, hosts, workers
- ``backend``   the cpu reference backend and the TPU lane backend
- ``models``    built-in workloads (phold, tgen-style traffic, ping)
- ``parallel``  device-mesh sharding of host lanes
- ``native``    managed OS processes under the preload shim
- ``faults``    fault schedules, routing overlays, the backend watchdog
- ``obs``       metrics, span tracing, netobs, flowtrace, the turn ledger
- ``sweep``     fleet sweeps: S scenarios through one vmapped kernel
- ``analysis``  shadowlint, the static determinism and lane-parity lint
- ``utils``     counters, pcap, logging, sim-stats

64-bit JAX mode is required: all simulation time is int64 nanoseconds (see
``core.time``).  Importing this package enables it; import ``shadow_tpu``
before the first ``jax`` trace.
"""

from jax import config as _jax_config

_jax_config.update("jax_enable_x64", True)

__version__ = "0.1.0"
