"""Launcher tests: hostfile parsing, include/exclude filters, world-info
encoding, runner command construction, per-node spawn (reference
tests/unit/test_run.py — pure logic, no cluster)."""
import base64
import json
import subprocess
import sys

import pytest

from deepspeed_tpu.launcher.launch import decode_world_info
from deepspeed_tpu.launcher.runner import (
    encode_world_info,
    fetch_hostfile,
    parse_args,
    parse_resource_filter,
)


@pytest.fixture
def hostfile(tmp_path):
    p = tmp_path / "hostfile"
    p.write_text(
        """
# comment line
worker-0 slots=4
worker-1 slots=4
worker-2 slots=2
""".strip()
    )
    return str(p)


def test_fetch_hostfile(hostfile):
    pool = fetch_hostfile(hostfile)
    assert pool == {"worker-0": 4, "worker-1": 4, "worker-2": 2}
    assert list(pool) == ["worker-0", "worker-1", "worker-2"]


def test_fetch_hostfile_missing_returns_empty(tmp_path):
    assert fetch_hostfile(str(tmp_path / "nope")) == {}


def test_fetch_hostfile_malformed(tmp_path):
    p = tmp_path / "bad"
    p.write_text("worker-0 gpus=4\n")
    with pytest.raises(ValueError, match="malformed"):
        fetch_hostfile(str(p))


def test_fetch_hostfile_duplicate(tmp_path):
    p = tmp_path / "dup"
    p.write_text("w slots=2\nw slots=4\n")
    with pytest.raises(ValueError, match="duplicate"):
        fetch_hostfile(str(p))


def test_include_filter(hostfile):
    pool = fetch_hostfile(hostfile)
    # whole-host include
    act = parse_resource_filter(pool, include_str="worker-1")
    assert act == {"worker-1": [0, 1, 2, 3]}
    # per-slot include
    act = parse_resource_filter(pool, include_str="worker-0:0,2@worker-2:1")
    assert act == {"worker-0": [0, 2], "worker-2": [1]}


def test_exclude_filter(hostfile):
    pool = fetch_hostfile(hostfile)
    act = parse_resource_filter(pool, exclude_str="worker-1")
    assert act == {"worker-0": [0, 1, 2, 3], "worker-2": [0, 1]}
    act = parse_resource_filter(pool, exclude_str="worker-0:1,3")
    assert act["worker-0"] == [0, 2]


def test_filter_validation(hostfile):
    pool = fetch_hostfile(hostfile)
    with pytest.raises(ValueError, match="mutually exclusive"):
        parse_resource_filter(pool, include_str="worker-0", exclude_str="worker-1")
    with pytest.raises(ValueError, match="not in hostfile"):
        parse_resource_filter(pool, include_str="worker-9")
    with pytest.raises(ValueError, match="invalid"):
        parse_resource_filter(pool, include_str="worker-2:5")


def test_world_info_roundtrip():
    active = {"a": [0, 1], "b": [0]}
    enc = encode_world_info(active)
    assert decode_world_info(enc) == active


def test_multinode_runner_commands(hostfile):
    from deepspeed_tpu.launcher.multinode_runner import OpenMPIRunner, PDSHRunner, SSHRunner

    args = parse_args(["--hostfile", hostfile, "--master_port", "29501", "train.py", "--lr", "0.1"])
    args.master_addr = "worker-0"
    pool = fetch_hostfile(hostfile)
    active = parse_resource_filter(pool)
    enc = encode_world_info(active)

    pdsh_cmd = PDSHRunner(args, enc).get_cmd({}, active)
    assert pdsh_cmd[0] == "pdsh"
    assert "worker-0,worker-1,worker-2" in pdsh_cmd
    assert "deepspeed_tpu.launcher.launch" in pdsh_cmd[-1]

    ssh_cmds = SSHRunner(args, enc).get_cmd({}, active)
    assert len(ssh_cmds) == 3 and all(c[0] == "ssh" for c in ssh_cmds)
    assert "--node_rank=2" in ssh_cmds[2][-1]

    mpi_cmd = OpenMPIRunner(args, enc).get_cmd({}, active)
    assert mpi_cmd[0] == "mpirun" and "train.py" in mpi_cmd


def test_launch_spawns_and_propagates_env(tmp_path):
    """End-to-end single-node: launch.py must spawn children with the
    rank/world env contract and propagate failure codes."""
    script = tmp_path / "child.py"
    # write to per-rank files — child stdout interleaves under the pack
    script.write_text(
        "import os\n"
        f"open(os.path.join({str(tmp_path)!r}, 'rank' + os.environ['RANK']), 'w').write(\n"
        "    os.environ['WORLD_SIZE'] + ':' + os.environ['MASTER_ADDR'] + ':' + os.environ['LOCAL_RANK'])\n"
    )
    enc = encode_world_info({"localhost": [0, 1]})
    res = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         "--node_rank=0", "--world_info", enc, "--procs_per_node", "2", str(script)],
        capture_output=True, text=True, timeout=60,
        env={"PATH": "/usr/bin:/bin", "PYTHONPATH": "/root/repo", "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, res.stderr
    assert (tmp_path / "rank0").read_text() == "2:127.0.0.1:0"
    assert (tmp_path / "rank1").read_text() == "2:127.0.0.1:1"


def test_launch_refuses_more_than_one_child_where_they_would_reach_for_the_chips(tmp_path):
    """One JAX process owns every chip of a host: several children are
    started only where JAX_PLATFORMS=cpu keeps them off the chips."""
    script = tmp_path / "child.py"
    script.write_text(f"open({str(tmp_path / 'ran')!r}, 'w').write('x')\n")
    enc = encode_world_info({"localhost": [0, 1, 2, 3]})
    res = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         "--node_rank=0", "--world_info", enc, str(script)],
        capture_output=True, text=True, timeout=60,
        env={"PATH": "/usr/bin:/bin", "PYTHONPATH": "/root/repo"},
    )
    assert res.returncode != 0
    assert "one JAX process owns every local chip" in res.stderr.replace("\n", " ")
    assert not (tmp_path / "ran").exists()
    # a single child needs no such promise
    res = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         "--node_rank=0", "--world_info", encode_world_info({"localhost": [0]}), str(script)],
        capture_output=True, text=True, timeout=60,
        env={"PATH": "/usr/bin:/bin", "PYTHONPATH": "/root/repo"},
    )
    assert res.returncode == 0, res.stderr
    assert (tmp_path / "ran").exists()


def test_launch_kills_pack_on_failure(tmp_path):
    script = tmp_path / "child.py"
    script.write_text(
        "import os, sys, time\n"
        "if os.environ['RANK'] == '1':\n"
        "    sys.exit(3)\n"
        "time.sleep(30)\n"
    )
    enc = encode_world_info({"localhost": [0, 1]})
    res = subprocess.run(
        [sys.executable, "-m", "deepspeed_tpu.launcher.launch",
         "--node_rank=0", "--world_info", enc, "--procs_per_node", "2", str(script)],
        capture_output=True, text=True, timeout=60,
        env={"PATH": "/usr/bin:/bin", "PYTHONPATH": "/root/repo", "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 3


def test_ds_ssh_local_fallback_and_hostfile(tmp_path):
    """bin/ds_ssh (reference bin/ds_ssh:1): no hostfile → run locally;
    with a hostfile it targets every parsed host (smoke-tested through
    the real hostfile parser with ssh unavailable → nonzero rc is fine,
    the parse path is what's under test)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    script = os.path.join(repo, "bin", "ds_ssh")
    env = dict(os.environ, PYTHONPATH=repo, DS_HOSTFILE=str(tmp_path / "none"))
    r = subprocess.run(
        [sys.executable, script, "echo", "local-ok"],
        env=env, capture_output=True, text=True, timeout=60,
    )
    assert r.returncode == 0 and "local-ok" in r.stdout
    assert "executing command locally" in r.stderr
    hf = tmp_path / "hostfile"
    hf.write_text("h1 slots=4\nh2 slots=4\n")
    r = subprocess.run(
        [sys.executable, script, "-H", str(hf), "true"],
        env=env, capture_output=True, text=True, timeout=60,
    )
    # ssh/pdsh to fake hosts fails, but both hosts must have been tried
    # (or pdsh invoked with the joined list) — no parse errors
    assert "malformed" not in r.stderr
    bad = tmp_path / "bad"
    bad.write_text("justahost\n")
    r = subprocess.run(
        [sys.executable, script, "-H", str(bad), "true"],
        env=env, capture_output=True, text=True, timeout=60,
    )
    assert r.returncode != 0 and "malformed" in (r.stderr + r.stdout)
