"""Launcher + elasticity tests (reference: tests/unit/launcher/,
tests/unit/elasticity/)."""

import subprocess
import sys

import pytest

from deepspeed_tpu.elasticity.elasticity import (compute_elastic_config,
                                                 get_valid_device_counts)
from deepspeed_tpu.launcher.runner import (decode_world_info, encode_world_info,
                                           filter_hosts, parse_args,
                                           parse_hostfile)
from deepspeed_tpu.runtime.config import ElasticityConfig
from deepspeed_tpu.runtime.config_utils import ConfigError


def test_parse_hostfile(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("""
# tpu pod hosts
worker-0 slots=4
worker-1 slots=4
worker-2   # defaults to 1 slot
""")
    hosts = parse_hostfile(str(hf))
    assert hosts == {"worker-0": 4, "worker-1": 4, "worker-2": 1}


def test_parse_hostfile_duplicate(tmp_path):
    hf = tmp_path / "hostfile"
    hf.write_text("a slots=1\na slots=2\n")
    with pytest.raises(ValueError):
        parse_hostfile(str(hf))


def test_filter_hosts():
    hosts = {"a": 1, "b": 1, "c": 1}
    assert list(filter_hosts(hosts, include="a,b")) == ["a", "b"]
    assert list(filter_hosts(hosts, exclude="b")) == ["a", "c"]
    with pytest.raises(ValueError):
        filter_hosts(hosts, include="zzz")
    with pytest.raises(ValueError):
        filter_hosts(hosts, exclude="a,b,c")


def test_world_info_roundtrip():
    hosts = {"w0": 4, "w1": 4}
    assert decode_world_info(encode_world_info(hosts)) == hosts


def test_args_parse_remainder():
    args = parse_args(["--hosts", "localhost", "train.py", "--lr", "1e-4"])
    assert args.script == "train.py"
    assert args.script_args == ["--lr", "1e-4"]


def test_local_launch_runs_script(tmp_path):
    script = tmp_path / "hello.py"
    script.write_text("import os, sys; sys.exit(0 if os.environ.get('FOO')=='bar' else 3)")
    from deepspeed_tpu.launcher import runner

    rc = runner.main(["--hosts", "localhost", "--env", "FOO=bar", str(script)])
    assert rc == 0


# ---------------------------------------------------------------------------
# elasticity
# ---------------------------------------------------------------------------


def test_valid_device_counts():
    # batch 24, micro batches {2,3}: n valid iff 24 % (2n)==0 or 24 % (3n)==0
    valid = get_valid_device_counts(24, [2, 3], 1, 12)
    assert 4 in valid and 12 in valid
    assert 5 not in valid


def test_compute_elastic_config():
    cfg = ElasticityConfig(enabled=True, max_train_batch_size=64,
                           micro_batch_sizes=[2, 4], min_device_count=1,
                           max_device_count=8)
    batch, valid, micro = compute_elastic_config(cfg)
    assert batch == 48  # maximizes coverage: valid for 6 of 8 device counts
    assert valid == [1, 2, 3, 4, 6, 8]
    for n, m in micro.items():
        assert batch % (m * n) == 0


def test_elastic_config_impossible():
    cfg = ElasticityConfig(enabled=True, max_train_batch_size=3,
                           micro_batch_sizes=[5], min_device_count=1,
                           max_device_count=2)
    with pytest.raises(ConfigError):
        compute_elastic_config(cfg)


# ---------------------------------------------------------------------------
# multinode runner backends (reference: multinode_runner.py PDSH/MPI/Slurm)
# ---------------------------------------------------------------------------


def test_runner_command_construction():
    from deepspeed_tpu.launcher.multinode_runner import get_runner

    hosts = {"nodeA": 1, "nodeB": 1}
    env = {"COORDINATOR_ADDRESS": "nodeA:8476", "NUM_PROCESSES": "2"}
    prog = ["python", "train.py", "--lr", "1e-4"]

    pdsh = get_runner("pdsh").get_cmd(env, hosts, prog)
    assert pdsh[0] == "pdsh" and "-w" in pdsh
    assert pdsh[pdsh.index("-w") + 1] == "nodeA,nodeB"
    assert "DSTPU_HOSTS=nodeA,nodeB" in pdsh[-1]
    assert "PDSH_RCMD_TYPE=ssh" in pdsh[-1]

    ompi = get_runner("openmpi").get_cmd(env, hosts, prog)
    assert ompi[:5] == ["mpirun", "-n", "2", "-npernode", "1"]
    assert "-x" in ompi and "COORDINATOR_ADDRESS=nodeA:8476" in ompi
    assert ompi[-4:] == prog

    mpich = get_runner("mpich").get_cmd(env, hosts, prog)
    assert mpich[:5] == ["mpirun", "-n", "2", "-ppn", "1"]
    assert "-genv" in mpich and "nodeA,nodeB" in mpich

    impi = get_runner("impi").get_cmd(env, hosts, prog)
    i = impi.index("-genv")
    genvs = {impi[j + 1]: impi[j + 2] for j in range(len(impi) - 2)
             if impi[j] == "-genv"}
    assert genvs.get("I_MPI_FABRICS") == "shm:ofi"

    slurm = get_runner("slurm").get_cmd(env, hosts, prog)
    assert slurm[0] == "srun" and "--ntasks-per-node=1" in slurm
    # env rides an env(1) prefix (argv is comma-safe; --export=K=V is not)
    assert "--export=ALL" in slurm and "env" in slurm
    assert "NUM_PROCESSES=2" in slurm
    assert get_runner("pdsh").local_env() == {"PDSH_RCMD_TYPE": "ssh"}

    ssh = get_runner("ssh")
    per = ssh.get_per_host_cmd("nodeB", env, prog)
    assert per[0] == "ssh" and per[-2] == "nodeB"
    assert "COORDINATOR_ADDRESS=nodeA:8476" in per[-1]

    with pytest.raises(ValueError, match="unknown launcher"):
        get_runner("kubectl")


def test_slurm_nodelist_expansion():
    from deepspeed_tpu.launcher.multinode_runner import expand_slurm_nodelist

    assert expand_slurm_nodelist("tpu[001-003,007],login1") == \
        ["tpu001", "tpu002", "tpu003", "tpu007", "login1"]
    assert expand_slurm_nodelist("single") == ["single"]
    assert expand_slurm_nodelist("a[1-2],b[10-11]") == \
        ["a1", "a2", "b10", "b11"]


def test_slurm_discovery_from_env(monkeypatch):
    from deepspeed_tpu.launcher import multinode_runner as mr

    monkeypatch.setenv("SLURM_JOB_NODELIST", "w[01-03]")
    monkeypatch.setattr(mr.shutil, "which", lambda _: None)
    assert mr.discover_slurm_hosts() == {"w01": 1, "w02": 1, "w03": 1}
    monkeypatch.delenv("SLURM_JOB_NODELIST")
    assert mr.discover_slurm_hosts() is None


# ---------------------------------------------------------------------------
# elastic agent (reference: elasticity/elastic_agent.py DSElasticAgent)
# ---------------------------------------------------------------------------


def test_elastic_agent_restarts_on_worker_failure(tmp_path):
    """Kill a worker mid-run; the agent re-rendezvouses WITHOUT the failed
    member and the survivors complete."""
    import sys
    from deepspeed_tpu.elasticity.elastic_agent import AgentConfig, ElasticAgent

    marker = tmp_path / "runs"
    marker.mkdir()
    script = tmp_path / "worker.py"
    script.write_text(f"""
import os, sys, time
member = os.environ["DSTPU_ELASTIC_MEMBER"]
restart = os.environ["DSTPU_RESTART_COUNT"]
n = os.environ["NUM_PROCESSES"]
open(r"{marker}" + f"/{{member}}-r{{restart}}-n{{n}}", "w").close()
if member == "hostB" and restart == "0":
    sys.exit(3)   # simulated hardware failure on first rendezvous
time.sleep(0.3)
""")
    def members_fn():
        # a health checker would evict the dead host after its crash
        if (marker / "hostB-r0-n3").exists():
            return ["hostA", "hostC"]
        return ["hostA", "hostB", "hostC"]

    agent = ElasticAgent(
        [sys.executable, str(script)], members_fn=members_fn,
        agent_config=AgentConfig(max_restarts=3, poll_interval_s=0.1,
                                 term_timeout_s=2.0))
    rc = agent.run()
    assert rc == 0
    runs = {p.name for p in marker.iterdir()}
    assert "hostB-r0-n3" in runs            # B ran in the first group
    assert any(r.startswith("hostA-r") and r.endswith("-n2") for r in runs), \
        runs                                 # re-rendezvous at world size 2
    assert any(r.startswith("hostC-r") and r.endswith("-n2") for r in runs)
    assert not any(r.startswith("hostB-r1") for r in runs)
    assert agent.restart_count >= 1


def test_elastic_agent_membership_change(tmp_path):
    """Members list shrinking triggers a group restart at the new size,
    clamped to a VALID world size by the elasticity batch math."""
    import sys
    from deepspeed_tpu.elasticity.elastic_agent import AgentConfig, ElasticAgent
    from deepspeed_tpu.runtime.config import ElasticityConfig

    marker = tmp_path / "runs"
    marker.mkdir()
    script = tmp_path / "worker.py"
    script.write_text(f"""
import os, time
m = os.environ["DSTPU_ELASTIC_MEMBER"]
open(r"{marker}" + "/" + m + "-n" + os.environ["NUM_PROCESSES"]
     + "-r" + os.environ["DSTPU_RESTART_COUNT"], "w").close()
time.sleep(1.0)
""")
    members = {"value": ["h1", "h2", "h3", "h4"]}

    def members_fn():
        # h4 leaves once the first group has demonstrably started
        if (marker / "h4-n4-r0").exists():
            members["value"] = ["h1", "h2", "h3"]
        return members["value"]

    # batch math: micro=2, max batch 8 → valid counts {1,2,4} for batch 8;
    # 3 members must clamp to 2
    agent = ElasticAgent(
        [sys.executable, str(script)], members_fn=members_fn,
        elastic_config=ElasticityConfig(
            enabled=True, max_train_batch_size=8, micro_batch_sizes=[2],
            min_device_count=1, max_device_count=4),
        agent_config=AgentConfig(max_restarts=3, poll_interval_s=0.3,
                                 term_timeout_s=2.0))
    rc = agent.run()
    assert rc == 0
    runs = {p.name for p in marker.iterdir()}
    assert "h4-n4-r0" in runs          # first group used all 4
    assert any(r == "h1-n2-r1" for r in runs), runs  # clamp 3 → 2
    assert not any(r.startswith("h3-n2") for r in runs)


def test_elastic_agent_bans_flapping_member(tmp_path):
    """A persistently failing member with a STATIC members_fn must not flap
    in and out: it is banned after its crash and the survivors finish."""
    import sys
    from deepspeed_tpu.elasticity.elastic_agent import AgentConfig, ElasticAgent

    marker = tmp_path / "runs"
    marker.mkdir()
    script = tmp_path / "worker.py"
    script.write_text(f"""
import os, sys, time
m = os.environ["DSTPU_ELASTIC_MEMBER"]
open(r"{marker}" + "/" + m + "-r" + os.environ["DSTPU_RESTART_COUNT"], "w").close()
if m == "bad":
    sys.exit(1)
time.sleep(1.0)
""")
    agent = ElasticAgent(
        [sys.executable, str(script)],
        members_fn=lambda: ["good1", "bad", "good2"],  # static: bad re-listed
        agent_config=AgentConfig(max_restarts=12, poll_interval_s=0.1,
                                 term_timeout_s=2.0, member_max_fails=2,
                                 rejoin_cooldown_s=0.15))
    rc = agent.run()
    assert rc == 0
    assert "bad" in agent.banned  # struck out after member_max_fails crashes
    runs = {p.name for p in marker.iterdir()}
    assert "bad-r0" in runs
    # crash → cool-down restart without bad → rejoin restart with bad →
    # second crash → banned; never launched again
    bad_runs = {r for r in runs if r.startswith("bad-")}
    assert len(bad_runs) == 2, bad_runs
    assert agent.restart_count <= 4


def test_elastic_agent_survives_cascading_crash(tmp_path):
    """Every worker exiting nonzero at once (coordinator death) must NOT ban
    the healthy hosts — the group restarts with full membership."""
    import sys
    import threading
    from deepspeed_tpu.elasticity.elastic_agent import AgentConfig, ElasticAgent

    state = tmp_path / "attempt"
    script = tmp_path / "worker.py"
    # first group: every worker exits 1, and all at one instant: a worker
    # marks itself, waits (bounded) until all three have, and leaves at a
    # time all three read off the newest marker.  Workers that crash as each
    # gets there (interpreters start 100s of ms apart on a loaded machine)
    # let a poll see one dead and two starting: that is the one-bad-host
    # case of the tests above, and the agent rightly restarts twice.
    # Later groups: clean exit.
    script.write_text(f"""
import os, sys, time
marks = [r"{state}" + "-" + h for h in ("h1", "h2", "h3")]
p = r"{state}" + "-" + os.environ["DSTPU_ELASTIC_MEMBER"]
if not os.path.exists(p):
    open(p, "w").close()
    give_up = time.monotonic() + 10.0
    while not all(map(os.path.exists, marks)) and time.monotonic() < give_up:
        time.sleep(0.005)
    if time.monotonic() < give_up:
        together = max(map(os.path.getmtime, marks)) + 0.3
        time.sleep(max(0.0, together - time.time()))
    os._exit(1)
time.sleep(0.2)
""")
    agent = ElasticAgent(
        [sys.executable, str(script)],
        members_fn=lambda: ["h1", "h2", "h3"],
        agent_config=AgentConfig(max_restarts=4, poll_interval_s=0.1,
                                 term_timeout_s=2.0))
    # the test's own limit: a group that never ends must fail here, not at
    # the suite's
    result = []
    runner = threading.Thread(target=lambda: result.append(agent.run()),
                              daemon=True)
    runner.start()
    runner.join(timeout=60.0)
    assert not runner.is_alive(), "the agent did not finish in 60 s"
    (rc,) = result
    assert rc == 0
    assert agent.banned == set()  # one synchronized crash bans nobody
    assert agent.restart_count == 1  # single restart with full membership


def test_natural_sorted_slurm_order():
    from deepspeed_tpu.launcher.multinode_runner import natural_sorted

    assert natural_sorted(["node10", "node2", "node1"]) == \
        ["node1", "node2", "node10"]


def test_elastic_agent_scale_up_with_debounce(tmp_path):
    """New members joining a HEALTHY group trigger ONE restart at the grown
    size — after the stability window, not per arrival."""
    import sys
    import time as _time
    from deepspeed_tpu.elasticity.elastic_agent import AgentConfig, ElasticAgent

    marker = tmp_path / "runs"
    marker.mkdir()
    script = tmp_path / "worker.py"
    script.write_text(f"""
import os, time
m = os.environ["DSTPU_ELASTIC_MEMBER"]
open(r"{marker}" + "/" + m + "-n" + os.environ["NUM_PROCESSES"]
     + "-r" + os.environ["DSTPU_RESTART_COUNT"], "w").close()
time.sleep({{}}.get(os.environ["DSTPU_RESTART_COUNT"], 6.0))
""".format("{'1': 0.6}"))
    members = {"value": ["h1", "h2"]}
    t0 = _time.monotonic()

    def members_fn():
        # two more hosts trickle in once the first group is running
        if (marker / "h1-n2-r0").exists():
            if len(members["value"]) == 2:
                members["value"] = ["h1", "h2", "h3"]
            elif (len(members["value"]) == 3
                    and _time.monotonic() - t0 > 1.0):
                members["value"] = ["h1", "h2", "h3", "h4"]
        return members["value"]

    agent = ElasticAgent(
        [sys.executable, str(script)], members_fn=members_fn,
        agent_config=AgentConfig(max_restarts=3, poll_interval_s=0.2,
                                 term_timeout_s=2.0, scale_up_delay_s=1.5))
    rc = agent.run()
    assert rc == 0
    runs = {p.name for p in marker.iterdir()}
    assert "h1-n2-r0" in runs            # started at 2
    assert "h4-n4-r1" in runs, runs      # ONE restart absorbed both joiners
    assert agent.restart_count == 1      # debounce: no restart at size 3
    assert not any(r.endswith("-n3-r1") for r in runs), runs
