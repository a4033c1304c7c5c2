"""Launcher tests: hostfile parsing, include/exclude filters, world-info
encoding, per-node process planning, multinode command construction.

Models reference tests/unit/test_run.py (hostfile + resource filter cases).
"""

import base64
import json
import subprocess
import sys

import pytest

from deeperspeed_tpu.launcher import (
    encode_world_info,
    fetch_hostfile,
    parse_args,
    parse_inclusion_exclusion,
    parse_resource_filter,
    plan_node_processes,
)
from deeperspeed_tpu.launcher.multinode_runner import (
    GCloudRunner,
    OpenMPIRunner,
    PDSHRunner,
    SSHRunner,
)


def _write_hostfile(tmp_path, text):
    p = tmp_path / "hostfile"
    p.write_text(text)
    return str(p)


class TestHostfile:
    def test_basic(self, tmp_path):
        path = _write_hostfile(tmp_path, "worker-0 slots=4\nworker-1 slots=8\n")
        pool = fetch_hostfile(path)
        assert list(pool.items()) == [("worker-0", 4), ("worker-1", 8)]

    def test_empty_lines_and_comments(self, tmp_path):
        path = _write_hostfile(
            tmp_path, "\n# head node\nworker-0 slots=4\n\nworker-1 slots=4\n"
        )
        pool = fetch_hostfile(path)
        assert list(pool) == ["worker-0", "worker-1"]

    def test_missing_returns_none(self, tmp_path):
        assert fetch_hostfile(str(tmp_path / "nope")) is None

    def test_malformed_raises(self, tmp_path):
        path = _write_hostfile(tmp_path, "worker-0 gpus=4\n")
        with pytest.raises(ValueError):
            fetch_hostfile(path)

    def test_duplicate_raises(self, tmp_path):
        path = _write_hostfile(tmp_path, "w0 slots=4\nw0 slots=2\n")
        with pytest.raises(ValueError):
            fetch_hostfile(path)


class TestResourceFilter:
    POOL = {"worker-0": 4, "worker-1": 4}

    def test_no_filter(self):
        active = parse_inclusion_exclusion(self.POOL, "", "")
        assert active == {"worker-0": [0, 1, 2, 3], "worker-1": [0, 1, 2, 3]}

    def test_include_whole_node(self):
        active = parse_inclusion_exclusion(self.POOL, "worker-1", "")
        assert active == {"worker-1": [0, 1, 2, 3]}

    def test_include_slots(self):
        active = parse_inclusion_exclusion(self.POOL, "worker-0@worker-1:0,2", "")
        assert active == {"worker-0": [0, 1, 2, 3], "worker-1": [0, 2]}

    def test_exclude_slot(self):
        active = parse_inclusion_exclusion(self.POOL, "", "worker-1:0")
        assert active == {"worker-0": [0, 1, 2, 3], "worker-1": [1, 2, 3]}

    def test_exclude_whole_node(self):
        active = parse_inclusion_exclusion(self.POOL, "", "worker-0")
        assert active == {"worker-1": [0, 1, 2, 3]}

    def test_mutually_exclusive(self):
        with pytest.raises(ValueError):
            parse_resource_filter(
                {"w": [0]}, include_str="w", exclude_str="w:0"
            )

    def test_unknown_host_raises(self):
        with pytest.raises(ValueError):
            parse_inclusion_exclusion(self.POOL, "worker-9", "")

    def test_unknown_slot_raises(self):
        with pytest.raises(ValueError):
            parse_inclusion_exclusion(self.POOL, "worker-0:9", "")

    def test_order_preserved(self):
        active = parse_inclusion_exclusion(self.POOL, "worker-1@worker-0", "")
        assert list(active) == ["worker-0", "worker-1"]


class TestWorldInfo:
    def test_roundtrip(self):
        info = {"w0": [0, 1], "w1": [0, 1, 2, 3]}
        blob = encode_world_info(info)
        decoded = json.loads(base64.urlsafe_b64decode(blob))
        assert decoded == info


class TestProcessPlanning:
    WORLD = {"w0": [0, 1, 2, 3], "w1": [0, 1, 2, 3]}

    def test_one_proc_per_node(self):
        plans = plan_node_processes(self.WORLD, node_rank=1, procs_per_node=1)
        assert len(plans) == 1
        (p,) = plans
        assert p["process_id"] == 1
        assert p["num_processes"] == 2
        assert p["world_size"] == 8
        assert p["chips"] == [0, 1, 2, 3]

    def test_proc_per_chip(self):
        plans = plan_node_processes(self.WORLD, node_rank=1, procs_per_node=4)
        assert [p["process_id"] for p in plans] == [4, 5, 6, 7]
        assert [p["chips"] for p in plans] == [[0], [1], [2], [3]]
        assert all(p["num_processes"] == 8 for p in plans)

    def test_uneven_slots(self):
        world = {"w0": [0, 1, 2], "w1": [0]}
        plans = plan_node_processes(world, node_rank=0, procs_per_node=2)
        assert [p["chips"] for p in plans] == [[0, 2], [1]]
        # w1 has 1 slot -> 1 proc; global process count = 2 + 1
        assert plans[0]["num_processes"] == 3

    def test_bad_node_rank(self):
        with pytest.raises(ValueError):
            plan_node_processes(self.WORLD, node_rank=5, procs_per_node=1)


def _args(extra):
    return parse_args(
        extra + ["train.py", "--lr", "0.1"]
    )


class TestRunnerCmds:
    RESOURCES = {"w0": [0, 1], "w1": [0, 1]}

    def test_pdsh_cmd(self):
        args = _args(["--master_addr", "10.0.0.1"])
        runner = PDSHRunner(args, "B64")
        runner.add_export("XLA_FLAGS", "--xla_foo")
        env = {}
        cmd = runner.get_cmd(env, self.RESOURCES)
        assert cmd[0] == "pdsh"
        assert "w0,w1" in cmd
        joined = " ".join(cmd)
        assert "--world_info=B64" in joined
        assert "--node_rank=%n" in joined
        assert "export XLA_FLAGS=--xla_foo;" in joined
        assert env["PDSH_RCMD_TYPE"] == "ssh"

    def test_ssh_cmd(self):
        args = _args(["--master_addr", "10.0.0.1"])
        runner = SSHRunner(args, "B64")
        cmd = runner.get_cmd({}, self.RESOURCES)
        assert cmd[:2] == ["bash", "-c"]
        script = cmd[2]
        assert script.count("ssh ") == 2
        assert "--node_rank=0" in script and "--node_rank=1" in script
        # per-child wait so a failing node fails the whole launch
        assert 'wait "$p" || rc=$?' in script
        assert script.strip().endswith("exit $rc")

    def test_ssh_cmd_quotes_spaced_exports(self):
        import shlex

        args = _args(["--master_addr", "10.0.0.1"])
        runner = SSHRunner(args, "B64")
        runner.add_export("XLA_FLAGS", "--xla_a --xla_b")
        script = runner.get_cmd({}, self.RESOURCES)[2]
        ssh_line = next(l for l in script.splitlines() if l.startswith("ssh "))
        remote = shlex.split(ssh_line.rstrip(" &"))[-1]
        # after the outer shell strips quoting, the remote command must
        # export the spaced value as ONE variable
        assert "export XLA_FLAGS='--xla_a --xla_b';" in remote

    def test_openmpi_cmd(self):
        args = _args(["--master_addr", "10.0.0.1"])
        runner = OpenMPIRunner(args, "B64", {"w0": 2, "w1": 2})
        cmd = runner.get_cmd({}, self.RESOURCES)
        assert cmd[0] == "mpirun"
        assert cmd[cmd.index("-n") + 1] == "4"

    def test_gcloud_cmd(self):
        args = _args(
            ["--master_addr", "10.0.0.1", "--tpu_name", "pod-1", "--zone", "us-central2-b"]
        )
        runner = GCloudRunner(args, "B64")
        cmd = runner.get_cmd({}, self.RESOURCES)
        assert cmd[:5] == ["gcloud", "compute", "tpus", "tpu-vm", "ssh"]
        assert "pod-1" in cmd
        assert "--worker=all" in cmd
        assert any(c.startswith("--command=") for c in cmd)
        assert "--zone=us-central2-b" in cmd

    def test_gcloud_requires_tpu_name(self):
        args = _args(["--master_addr", "x"])
        runner = GCloudRunner(args, "B64")
        with pytest.raises(ValueError):
            runner.get_cmd({}, self.RESOURCES)


class TestEndToEndLocal:
    def test_single_node_launch_spawns_script(self, tmp_path):
        """Run the per-node launcher for real with 2 procs on this host and
        check that env (RANK, DS_PROCESS_ID, chip visibility) is correct."""
        script = tmp_path / "probe.py"
        script.write_text(
            "import os, json, sys\n"
            "out = {k: os.environ.get(k) for k in"
            " ['RANK','LOCAL_RANK','WORLD_SIZE','DS_PROCESS_ID',"
            "'DS_NUM_PROCESSES','DS_COORDINATOR_ADDRESS','TPU_VISIBLE_CHIPS']}\n"
            "path = os.path.join(os.path.dirname(__file__),"
            " f\"out_{os.environ['RANK']}.json\")\n"
            "json.dump(out, open(path, 'w'))\n"
        )
        world = encode_world_info({"localhost": [0, 1]})
        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "deeperspeed_tpu.launcher.launch",
                f"--world_info={world}",
                "--master_addr=127.0.0.1",
                "--master_port=29999",
                "--procs_per_node=2",
                "--node_rank=0",
                str(script),
            ],
            capture_output=True,
            timeout=120,
        )
        assert proc.returncode == 0, proc.stderr.decode()
        outs = [
            json.load(open(tmp_path / f"out_{r}.json")) for r in (0, 1)
        ]
        assert [o["RANK"] for o in outs] == ["0", "1"]
        assert all(o["WORLD_SIZE"] == "2" for o in outs)
        assert all(
            o["DS_COORDINATOR_ADDRESS"] == "127.0.0.1:29999" for o in outs
        )
        assert [o["TPU_VISIBLE_CHIPS"] for o in outs] == ["0", "1"]

    def test_failing_child_propagates(self, tmp_path):
        script = tmp_path / "boom.py"
        script.write_text("import sys; sys.exit(3)\n")
        world = encode_world_info({"localhost": [0]})
        proc = subprocess.run(
            [
                sys.executable,
                "-m",
                "deeperspeed_tpu.launcher.launch",
                f"--world_info={world}",
                "--node_rank=0",
                str(script),
            ],
            capture_output=True,
            timeout=120,
        )
        assert proc.returncode == 3


class TestDistributedDiscovery:
    def test_ds_env(self, monkeypatch):
        from deeperspeed_tpu.utils import distributed

        monkeypatch.setenv("DS_COORDINATOR_ADDRESS", "1.2.3.4:29500")
        monkeypatch.setenv("DS_NUM_PROCESSES", "4")
        monkeypatch.setenv("DS_PROCESS_ID", "2")
        found = distributed.discover()
        assert found == dict(
            coordinator_address="1.2.3.4:29500", num_processes=4, process_id=2
        )

    def test_legacy_env(self, monkeypatch):
        from deeperspeed_tpu.utils import distributed

        monkeypatch.delenv("DS_COORDINATOR_ADDRESS", raising=False)
        monkeypatch.setenv("MASTER_ADDR", "5.6.7.8")
        monkeypatch.setenv("MASTER_PORT", "1234")
        monkeypatch.setenv("WORLD_SIZE", "2")
        monkeypatch.setenv("RANK", "1")
        found = distributed.discover()
        assert found == dict(
            coordinator_address="5.6.7.8:1234", num_processes=2, process_id=1
        )

    def test_mpi_env(self, monkeypatch):
        from deeperspeed_tpu.utils import distributed

        for k in ("DS_COORDINATOR_ADDRESS", "MASTER_ADDR", "WORLD_SIZE", "RANK"):
            monkeypatch.delenv(k, raising=False)
        monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "8")
        monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "3")
        found = distributed.discover()
        assert found["num_processes"] == 8 and found["process_id"] == 3

    def test_single_process_fallback(self, monkeypatch):
        from deeperspeed_tpu.utils import distributed

        for k in (
            "DS_COORDINATOR_ADDRESS",
            "MASTER_ADDR",
            "WORLD_SIZE",
            "RANK",
            "OMPI_COMM_WORLD_SIZE",
        ):
            monkeypatch.delenv(k, raising=False)
        assert distributed.init_distributed() is False


def test_env_report_runs():
    proc = subprocess.run(
        [sys.executable, "-m", "deeperspeed_tpu.env_report"],
        capture_output=True,
        timeout=300,
    )
    assert proc.returncode == 0, proc.stderr.decode()
    out = proc.stdout.decode()
    assert "native op report" in out
    assert "jax version" in out


def test_aml_env_discovery(monkeypatch):
    """AzureML env maps onto the standard discovery (reference
    utils/distributed.py:99-137)."""
    import os

    from deeperspeed_tpu.utils import distributed as dist_mod

    # patch_aml_env writes MASTER_*/RANK/WORLD_SIZE directly into
    # os.environ; snapshot and restore so nothing leaks into later tests
    vars_touched = ("MASTER_ADDR", "MASTER_PORT", "RANK", "WORLD_SIZE",
                    "DS_COORDINATOR_ADDRESS")
    saved = {v: os.environ.get(v) for v in vars_touched}
    for var in vars_touched:
        monkeypatch.delenv(var, raising=False)
    monkeypatch.setenv("AZUREML_EXPERIMENT_ID", "exp123")
    monkeypatch.setenv("AZ_BATCH_MASTER_NODE", "10.0.0.5:6105")
    monkeypatch.setenv("OMPI_COMM_WORLD_RANK", "3")
    monkeypatch.setenv("OMPI_COMM_WORLD_SIZE", "8")
    try:
        assert dist_mod.in_aml()
        found = dist_mod.discover()
        assert found["coordinator_address"] == "10.0.0.5:29500"
        assert found["process_id"] == 3 and found["num_processes"] == 8
    finally:
        for v, old in saved.items():
            if old is None:
                os.environ.pop(v, None)
            else:
                os.environ[v] = old


class TestLauncherParentStaysOffTheChip:
    """A chip belongs to one process: the launcher parent must count chips
    and spawn its children without ever building a JAX backend."""

    def test_chip_count_sources(self, monkeypatch):
        from deeperspeed_tpu.launcher import runner

        monkeypatch.setenv("TPU_VISIBLE_CHIPS", "0,1,2,3")
        assert runner._local_chip_count() == 4
        monkeypatch.delenv("TPU_VISIBLE_CHIPS")
        monkeypatch.setattr(
            runner.glob, "glob",
            lambda pat: ["/dev/vfio/0", "/dev/vfio/1"] if "vfio" in pat else [])
        assert runner._local_chip_count() == 2
        monkeypatch.setattr(runner.glob, "glob", lambda pat: [])
        with pytest.raises(RuntimeError, match="--num_chips"):
            runner._local_chip_count()

    def test_single_node_launch_builds_no_backend(self, tmp_path):
        code = (
            "import subprocess, sys\n"
            "from deeperspeed_tpu.launcher import runner\n"
            "class Done:\n"
            "    returncode = 0\n"
            "    def __init__(self, cmd, env=None):\n"
            "        print('CMD', ' '.join(cmd))\n"
            "    def wait(self):\n"
            "        return 0\n"
            "subprocess.Popen = Done\n"
            f"runner.main(['--hostfile', r'{tmp_path}/none', '--num_chips',"
            " '2', 'train.py'])\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge.backends_are_initialized()\n"
            "print('PARENT OFF THE CHIP')\n"
        )
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=300)
        assert proc.returncode == 0, proc.stderr
        assert "PARENT OFF THE CHIP" in proc.stdout
        world = [t for t in proc.stdout.split() if t.startswith("--world_info")]
        info = json.loads(base64.urlsafe_b64decode(world[0].split("=", 1)[1]))
        assert info == {"localhost": [0, 1]}
