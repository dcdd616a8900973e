import pytest

from shadow_tpu.core import config, simtime
from shadow_tpu.core.config import (
    ConfigError,
    FinalState,
    LogLevel,
    QDiscMode,
    load_config_str,
    to_processed_dict,
)

BASIC = """
general:
  stop_time: 10s
  model_unblocked_syscall_latency: true
network:
  graph:
    type: 1_gbit_switch
hosts:
  server:
    network_node_id: 0
    processes:
    - path: python3
      args: -m http.server 80
      start_time: 3s
      expected_final_state: running
  client1: &client_host
    network_node_id: 0
    processes:
    - path: curl
      args: -s server
      start_time: 5s
  client2: *client_host
  client3: *client_host
"""


def test_basic_file_transfer_shape():
    cfg = load_config_str(BASIC)
    assert cfg.general.stop_time == 10 * simtime.SECOND
    assert cfg.general.model_unblocked_syscall_latency is True
    assert cfg.network.graph.type == "1_gbit_switch"
    assert set(cfg.hosts) == {"server", "client1", "client2", "client3"}
    srv = cfg.hosts["server"].processes[0]
    assert srv.path == "python3"
    assert srv.args == ["-m", "http.server", "80"]
    assert srv.start_time == 3 * simtime.SECOND
    assert srv.expected_final_state.kind == FinalState.RUNNING
    # YAML anchors give clients identical process lists
    assert cfg.hosts["client2"].processes[0].path == "curl"


def test_inline_gml_and_bare_seconds():
    cfg = load_config_str(
        """
general:
  stop_time: 300
network:
  graph:
    type: gml
    inline: "graph []"
hosts:
  a: {network_node_id: 0}
"""
    )
    assert cfg.general.stop_time == 300 * simtime.SECOND
    assert cfg.network.graph.inline == "graph []"


def test_overrides_win_over_file():
    cfg = load_config_str(BASIC, overrides={"general": {"seed": 99, "stop_time": "5s"}})
    assert cfg.general.seed == 99
    assert cfg.general.stop_time == 5 * simtime.SECOND
    # untouched fields keep file/default values
    assert cfg.general.model_unblocked_syscall_latency is True


def test_extension_keys_ignored():
    cfg = load_config_str(
        """
x-anchors:
  common: {foo: 1}
general:
  stop_time: 1s
hosts:
  a: {network_node_id: 0}
"""
    )
    assert "a" in cfg.hosts


def test_unknown_keys_rejected():
    with pytest.raises(ConfigError, match="unknown option"):
        load_config_str("general: {stop_time: 1s, frobnicate: 2}\nhosts: {a: {}}")
    with pytest.raises(ConfigError, match="unknown top-level"):
        load_config_str("general: {stop_time: 1s}\nbogus: {}\nhosts: {a: {}}")


def test_required_fields():
    with pytest.raises(ConfigError, match="stop_time"):
        load_config_str("hosts: {a: {}}")
    with pytest.raises(ConfigError, match="at least one host"):
        load_config_str("general: {stop_time: 1s}")


def test_expected_final_state_forms():
    cfg = load_config_str(
        """
general: {stop_time: 1s}
hosts:
  a:
    processes:
    - {path: /bin/true, expected_final_state: {exited: 3}}
    - {path: /bin/kill, expected_final_state: {signaled: 9}}
"""
    )
    p0, p1 = cfg.hosts["a"].processes
    assert (p0.expected_final_state.kind, p0.expected_final_state.value) == (FinalState.EXITED, 3)
    assert (p1.expected_final_state.kind, p1.expected_final_state.value) == (FinalState.SIGNALED, 9)


def test_experimental_and_host_defaults():
    cfg = load_config_str(
        """
general: {stop_time: 1s, log_level: debug}
experimental:
  runahead: 5ms
  interface_qdisc: round-robin
  use_dynamic_runahead: true
host_defaults:
  pcap_enabled: true
hosts:
  a:
    bandwidth_down: 100 Mbit
    bandwidth_up: 50 Mbit
"""
    )
    assert cfg.general.log_level == LogLevel.DEBUG
    assert cfg.experimental.runahead == 5 * simtime.MILLISECOND
    assert cfg.experimental.interface_qdisc == QDiscMode.ROUND_ROBIN
    assert cfg.host_defaults.pcap_enabled is True
    assert cfg.hosts["a"].bandwidth_down == 10**8
    assert cfg.hosts["a"].bandwidth_up == 5 * 10**7


def test_graph_validation():
    with pytest.raises(ConfigError, match="exactly one"):
        load_config_str(
            "general: {stop_time: 1s}\nnetwork: {graph: {type: gml}}\nhosts: {a: {}}"
        )
    with pytest.raises(ConfigError, match="unknown type"):
        load_config_str(
            "general: {stop_time: 1s}\nnetwork: {graph: {type: petersen}}\nhosts: {a: {}}"
        )


def test_processed_config_roundtrip():
    cfg = load_config_str(BASIC)
    d = to_processed_dict(cfg)
    assert d["general"]["stop_time"] == 10 * simtime.SECOND
    assert d["hosts"]["server"]["processes"][0]["path"] == "python3"
    # must be YAML-serializable
    import yaml

    yaml.safe_dump(d)


def test_hostname_validation():
    with pytest.raises(ConfigError, match="invalid hostname"):
        load_config_str("general: {stop_time: 1s}\nhosts: {'bad host!': {}}")
    cfg = load_config_str("general: {stop_time: 1s}\nhosts: {'lossy.tcpserver.echo': {}}")
    assert "lossy.tcpserver.echo" in cfg.hosts


@pytest.mark.parametrize("block,option", [
    ("experimental", "plane_kernel: pallas"),
    ("faults", "kernel_fallback: true"),
])
def test_plane_kernel_flag_validates(block, option):
    """The removed kernel selector and its fallback switch are unknown
    options now: a config that still sets either is refused loudly at
    parse time, never silently ignored."""
    assert not hasattr(config.ConfigOptions().experimental, "plane_kernel")
    assert not hasattr(config.ConfigOptions().faults, "kernel_fallback")
    with pytest.raises(ConfigError, match="unknown option"):
        load_config_str(
            BASIC.replace("general:", f"{block}:\n  {option}\ngeneral:"))


def test_workload_block_yaml11_spellings():
    """The `workload:` block survives YAML 1.1's bare off/on-as-bool at
    BOTH levels — the whole block and the scenario field — like
    telemetry.sink and strace_logging_mode (docs/workloads.md)."""
    # block level: `workload: off` parses as boolean False
    cfg = load_config_str(BASIC.replace("general:", "workload: off\ngeneral:"))
    assert cfg.workload.enabled is False
    assert cfg.workload.scenario is None
    cfg = load_config_str(BASIC.replace("general:", "workload: on\ngeneral:"))
    assert cfg.workload.enabled is True
    # field level: `scenario: off` -> the "off" sentinel, `scenario: on`
    # -> None ("enabled at the default path")
    cfg = load_config_str(BASIC.replace(
        "general:", "workload:\n  enabled: true\n  scenario: off\ngeneral:"))
    assert cfg.workload.scenario == "off"
    cfg = load_config_str(BASIC.replace(
        "general:", "workload:\n  enabled: true\n  scenario: on\ngeneral:"))
    assert cfg.workload.scenario is None


def test_workload_block_fields_validate():
    cfg = load_config_str(BASIC.replace(
        "general:",
        "workload:\n  scenario: scenarios/incast.yaml\n  seed: 3\ngeneral:"))
    assert cfg.workload.scenario == "scenarios/incast.yaml"
    assert cfg.workload.seed == 3
    assert cfg.workload.enabled is False
    with pytest.raises(ConfigError, match="workload.seed"):
        load_config_str(BASIC.replace(
            "general:", "workload:\n  seed: -1\ngeneral:"))
    with pytest.raises(ConfigError, match="unknown option"):
        load_config_str(BASIC.replace(
            "general:", "workload:\n  bogus: 1\ngeneral:"))
    with pytest.raises(ConfigError, match="scenario"):
        load_config_str(BASIC.replace(
            "general:", "workload:\n  scenario: 7\ngeneral:"))
