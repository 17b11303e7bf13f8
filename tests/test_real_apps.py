"""Real off-the-shelf software end-to-end (the reference's examples gate,
examples/apps/: curl, nginx, iperf...): an UNMODIFIED CPython http.server
daemon and an unmodified curl client talk HTTP over the SIMULATED TCP
stack, deterministically.

This exercises the whole managed-process surface at once: multi-hundred-
syscall interpreter startup, simulated getaddrinfo resolution, listen/
accept/poll/send/recv on simulated stream sockets, simulated clock (the
HTTP Date header shows year 2000), deterministic entropy (CPython's hash
seed comes from the shim's getrandom), and the raw-syscall backstop for
everything glibc does internally.
"""

import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from shadow_tpu.config.options import ConfigOptions
from shadow_tpu.engine.sim import Simulation

REPO = Path(__file__).resolve().parents[1]
CURL = shutil.which("curl")
# the system interpreter, NOT the venv one: the guest is a plain Python
# HTTP server and needs none of the venv's packages
PY = "/usr/bin/python3" if Path("/usr/bin/python3").exists() else sys.executable


@pytest.fixture(scope="module", autouse=True)
def native_build():
    subprocess.run(
        ["make", "-C", str(REPO / "native")], check=True, capture_output=True
    )


def _run(tmp_path: Path, tag: str):
    import os

    docroot = tmp_path / tag / "www"
    docroot.mkdir(parents=True)
    (docroot / "hello.txt").write_text("simulated internet says hello\n")
    # pin the REAL mtime: the Last-Modified header reflects it, and the
    # determinism check diffs the full client output
    os.utime(docroot / "hello.txt", (946684800, 946684800))
    data = tmp_path / tag / "data"
    cfg = ConfigOptions.from_yaml(
        f"""
general: {{stop_time: 30s, seed: 11, data_directory: {data}, heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
hosts:
  www:
    network_node_id: 0
    processes:
      - path: {PY}
        args: [-m, http.server, "8080", --bind, 0.0.0.0, --directory, {docroot}]
        expected_final_state: running
  client:
    network_node_id: 0
    processes:
      - path: {CURL}
        args: [-s, -i, --max-time, "20", http://www:8080/hello.txt]
        start_time: 2s
"""
    )
    result = Simulation(cfg).run()
    out = (data / "hosts" / "client" / "curl.stdout").read_text()
    return result, out


@pytest.mark.skipif(CURL is None, reason="curl not installed")
def test_python_httpd_curl_over_simulated_tcp(tmp_path):
    result, out = _run(tmp_path, "a")
    assert "HTTP/1.0 200 OK" in out  # shim warnings share the stream
    assert "simulated internet says hello" in out
    # the HTTP Date header comes from the SIMULATED clock: 2000-01-01
    # plus a couple of simulated seconds, never the real 2026 clock
    assert "Date: Sat, 01 Jan 2000" in out
    assert "Server: SimpleHTTP" in out
    assert not result.process_errors


@pytest.mark.skipif(CURL is None, reason="curl not installed")
def test_python_httpd_curl_deterministic(tmp_path):
    """Run-twice determinism over the real-software stack: byte-identical
    client output including the simulated-time headers."""
    _, out1 = _run(tmp_path, "r1")
    _, out2 = _run(tmp_path, "r2")
    assert out1 == out2


IP_BIN = "/usr/sbin/ip" if Path("/usr/sbin/ip").exists() else shutil.which("ip")


def _run_ip(tmp_path: Path, tag: str):
    data = tmp_path / tag / "data"
    cfg = ConfigOptions.from_yaml(
        f"""
general: {{stop_time: 5s, seed: 4, data_directory: {data}, heartbeat_interval: null}}
network: {{graph: {{type: 1_gbit_switch}}}}
hosts:
  router:
    network_node_id: 0
    processes:
      - path: {IP_BIN}
        args: [addr, show]
"""
    )
    result = Simulation(cfg).run()
    return result, (data / "hosts" / "router" / "ip.stdout").read_text()


@pytest.mark.skipif(IP_BIN is None, reason="iproute2 not installed")
def test_iproute2_sees_simulated_interfaces(tmp_path):
    """An UNMODIFIED iproute2 `ip addr show` enumerates the SIMULATED
    interfaces over the emulated AF_NETLINK(NETLINK_ROUTE) dump surface
    (the reference's socket/netlink.rs answers the same requests): lo +
    eth0 with the host's simulated 11.0.0.0/8 address — never the real
    machine's interfaces."""
    result, out = _run_ip(tmp_path, "a")
    assert "1: lo:" in out and "LOOPBACK" in out
    assert "inet 127.0.0.1/8" in out
    assert "2: eth0:" in out
    assert "inet 11.0.0.1/8" in out  # the simulated address, /8 assignment
    assert "state UP" in out
    # deterministic MAC derived from the simulated IP
    assert "link/ether 02:54:0b:00:00:01" in out
    assert not result.process_errors


@pytest.mark.skipif(IP_BIN is None, reason="iproute2 not installed")
def test_iproute2_netlink_deterministic(tmp_path):
    _, out1 = _run_ip(tmp_path, "r1")
    _, out2 = _run_ip(tmp_path, "r2")
    assert out1 == out2


WGET = shutil.which("wget")
GIT = shutil.which("git")


def _run_multihop(tmp_path: Path, tag: str):
    """BASELINE config #5's stand-in (tor isn't installable here): a
    3-hop chain topology with CONCURRENT flows from three distinct real
    client binaries — curl, wget, and a full `git clone` over HTTP (git
    spawns git-remote-http, itself a libcurl app) — against CPython
    http.server daemons at the far end."""
    import os

    base = tmp_path / tag
    docroot = base / "www"
    docroot.mkdir(parents=True)
    (docroot / "a.txt").write_text("multihop says hello\n")
    os.utime(docroot / "a.txt", (946684800, 946684800))
    # a real git repo served over the dumb-http protocol
    src = base / "src"
    src.mkdir()
    subprocess.run(["git", "init", "-q"], cwd=src, check=True)
    (src / "f.txt").write_text("simulated clone payload\n")
    subprocess.run(["git", "add", "f.txt"], cwd=src, check=True)
    subprocess.run(
        ["git", "-c", "user.email=a@b", "-c", "user.name=t",
         "commit", "-qm", "init"],
        cwd=src, check=True,
        env={**os.environ,
             "GIT_AUTHOR_DATE": "2000-01-01T00:00:00Z",
             "GIT_COMMITTER_DATE": "2000-01-01T00:00:00Z"},
    )
    gitroot = base / "gitroot"
    gitroot.mkdir()
    subprocess.run(
        ["git", "clone", "-q", "--bare", str(src), str(gitroot / "repo.git")],
        check=True,
    )
    subprocess.run(
        ["git", "update-server-info"], cwd=gitroot / "repo.git", check=True
    )
    clone_dst = base / "cloned"
    data = base / "data"
    cfg = ConfigOptions.from_yaml(
        f"""
general: {{stop_time: 60s, seed: 17, data_directory: {data}, heartbeat_interval: null}}
network:
  graph:
    type: gml
    inline: |
      graph [
        directed 0
        node [ id 0 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        node [ id 1 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        node [ id 2 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        node [ id 3 host_bandwidth_up "100 Mbit" host_bandwidth_down "100 Mbit" ]
        edge [ source 0 target 0 latency "1 ms" ]
        edge [ source 3 target 3 latency "1 ms" ]
        edge [ source 0 target 1 latency "5 ms" ]
        edge [ source 1 target 2 latency "8 ms" ]
        edge [ source 2 target 3 latency "12 ms" ]
      ]
hosts:
  www:
    network_node_id: 0
    processes:
      - path: {PY}
        args: [-m, http.server, "8080", --bind, 0.0.0.0, --directory, {docroot}]
        expected_final_state: running
  gitsrv:
    network_node_id: 0
    processes:
      - path: {PY}
        args: [-m, http.server, "8081", --bind, 0.0.0.0, --directory, {gitroot}]
        expected_final_state: running
  curlc:
    network_node_id: 3
    processes:
      - path: {CURL}
        args: [-s, -i, --max-time, "30", http://www:8080/a.txt]
        start_time: 2s
  wgetc:
    network_node_id: 3
    processes:
      - path: {WGET}
        args: [-q, -O, "-", -T, "30", http://www:8080/a.txt]
        start_time: 2s
  gitc:
    network_node_id: 3
    processes:
      - path: {GIT}
        args: [clone, -q, "http://gitsrv:8081/repo.git", {clone_dst / tag}]
        start_time: 3s
"""
    )
    result = Simulation(cfg).run()
    return result, data, clone_dst / tag


@pytest.mark.skipif(
    CURL is None or WGET is None or GIT is None,
    reason="curl/wget/git not all installed",
)
def test_multihop_concurrent_real_clients(tmp_path):
    result, data, cloned = _run_multihop(tmp_path, "a")
    curl_out = (data / "hosts" / "curlc" / "curl.stdout").read_text()
    wget_out = (data / "hosts" / "wgetc" / "wget.stdout").read_text()
    assert "HTTP/1.0 200 OK" in curl_out
    assert "multihop says hello" in curl_out
    assert wget_out == "multihop says hello\n"
    # the git clone really happened THROUGH the simulated 3-hop network
    assert (cloned / "f.txt").read_text() == "simulated clone payload\n"
    assert not result.process_errors


@pytest.mark.skipif(
    CURL is None or WGET is None or GIT is None,
    reason="curl/wget/git not all installed",
)
def test_multihop_deterministic(tmp_path):
    _, d1, _ = _run_multihop(tmp_path, "r1")
    _, d2, _ = _run_multihop(tmp_path, "r2")
    for host, f in (("curlc", "curl.stdout"), ("wgetc", "wget.stdout")):
        a = (d1 / "hosts" / host / f).read_text()
        b = (d2 / "hosts" / host / f).read_text()
        assert a == b, f"{host}/{f} differs between runs"
