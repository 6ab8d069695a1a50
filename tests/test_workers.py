"""Worker process-management tests (parity model: reference
tests/test_worker_process_runtime.py + lifecycle behavior, using real
short-lived subprocesses instead of the real controller)."""

import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

from comfyui_distributed_tpu.utils.exceptions import ProcessError
from comfyui_distributed_tpu.utils.process import is_process_alive
from comfyui_distributed_tpu.workers.launch_builder import (
    build_launch_command,
    split_extra_args,
)
from comfyui_distributed_tpu.workers.lifecycle import (
    ManagedProcess,
    kill_process_tree,
)
from comfyui_distributed_tpu.workers.process_manager import WorkerProcessManager


class TestLaunchBuilder:
    def test_argv_and_env(self):
        argv, env = build_launch_command(
            {"id": "w1", "address": "http://10.0.0.2:8289", "mesh_devices": 4},
            master_port=8288, config_path="/tmp/cfg.json")
        assert argv[:3] == [sys.executable, "-m", "comfyui_distributed_tpu"]
        assert "--port" in argv and "8289" in argv
        assert env["CDT_IS_WORKER"] == "1"
        assert env["CDT_WORKER_ID"] == "w1"
        assert env["CDT_MASTER_PORT"] == "8288"
        assert env["CDT_MESH_DEVICES"] == "4"
        assert env["CDT_CONFIG_PATH"] == "/tmp/cfg.json"
        assert int(env["CDT_MASTER_PID"]) == os.getpid()

    def test_explicit_port_field_wins(self):
        argv, _ = build_launch_command(
            {"id": "w1", "port": 9001, "address": "http://h:8000"}, 8288)
        assert "9001" in argv

    def test_no_port_raises(self):
        with pytest.raises(ProcessError):
            build_launch_command({"id": "w1", "address": "http://h"}, 8288)

    def test_extra_args_split(self):
        assert split_extra_args("--foo 1 --bar 'a b'") == ["--foo", "1", "--bar", "a b"]
        assert split_extra_args("") == []

    @pytest.mark.parametrize("bad", ["--x; rm -rf /", "a && b", "`cmd`", "$(x)", "a|b"])
    def test_shell_metacharacters_rejected(self, bad):
        with pytest.raises(ProcessError):
            split_extra_args(bad)


class TestLifecycle:
    def test_kill_process_tree(self):
        proc = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(60)"],
                                start_new_session=True)
        assert is_process_alive(proc.pid)
        assert kill_process_tree(proc.pid, grace=2.0)
        proc.wait(timeout=5)
        assert not is_process_alive(proc.pid)

    def test_managed_process_liveness(self):
        proc = subprocess.Popen([sys.executable, "-c", "pass"])
        mp = ManagedProcess("w1", proc)
        proc.wait(timeout=10)
        assert not mp.is_alive()


class TestWorkerMonitor:
    def test_monitor_kills_worker_when_master_dies(self, tmp_path):
        """Spawn a fake master (short sleep), run the monitor wrapping a
        long-lived worker; when the master exits, the monitor must kill
        the worker (reference workers/worker_monitor.py:94-106)."""
        monitor = Path("comfyui_distributed_tpu/workers/worker_monitor.py").resolve()
        master = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(4)"])
        pid_file = tmp_path / "pids"
        env = {**os.environ, "CDT_MASTER_PID": str(master.pid),
               "CDT_PID_FILE": str(pid_file), "CDT_MONITOR_POLL": "0.2"}
        mon = subprocess.Popen(
            [sys.executable, str(monitor), sys.executable, "-c",
             "import time; time.sleep(120)"],
            env=env)
        # wait for pid file (generous: interpreter start can starve under
        # concurrent suite load)
        for _ in range(300):
            if pid_file.exists() and "," in pid_file.read_text():
                break
            time.sleep(0.1)
        _, worker_pid = map(int, pid_file.read_text().split(","))
        assert is_process_alive(worker_pid)
        master.wait(timeout=30)
        mon.wait(timeout=30)          # monitor exits after killing worker
        time.sleep(0.3)
        assert not is_process_alive(worker_pid)

    def test_monitor_propagates_worker_exit(self):
        monitor = Path("comfyui_distributed_tpu/workers/worker_monitor.py").resolve()
        env = {**os.environ, "CDT_MASTER_PID": str(os.getpid()),
               "CDT_MONITOR_POLL": "0.1"}
        mon = subprocess.Popen(
            [sys.executable, str(monitor), sys.executable, "-c", "exit(3)"], env=env)
        assert mon.wait(timeout=60) == 3


class TestProcessManager:
    def _manager_with_fake_launch(self, tmp_config, monkeypatch, procs):
        from comfyui_distributed_tpu.utils import config as config_mod
        from comfyui_distributed_tpu.workers import process_manager as pm

        config_mod.update_config(lambda c: c["hosts"].append(
            {"id": "w1", "address": "http://127.0.0.1:9001", "enabled": True,
             "type": "local"}))

        def fake_launch(worker, master_port, config_path=None,
                        use_watchdog=True, log_dir=None):
            proc = subprocess.Popen([sys.executable, "-c",
                                     "import time; time.sleep(30)"],
                                    start_new_session=True)
            procs.append(proc)
            return ManagedProcess(worker["id"], proc)

        monkeypatch.setattr(pm, "launch_worker_process", fake_launch)
        return WorkerProcessManager()

    def test_launch_stop_cycle_and_persistence(self, tmp_config, monkeypatch):
        from comfyui_distributed_tpu.utils import config as config_mod

        procs = []
        try:
            mgr = self._manager_with_fake_launch(tmp_config, monkeypatch, procs)
            mp = mgr.launch_worker("w1")
            assert mgr.get_managed_workers()["w1"]["pid"] == mp.pid
            # persisted into config
            cfg = config_mod.load_config()
            assert cfg["managed_processes"]["w1"]["pid"] == mp.pid
            # double launch refused
            with pytest.raises(ProcessError):
                mgr.launch_worker("w1")
            assert mgr.stop_worker("w1")
            assert mgr.get_managed_workers() == {}
            assert config_mod.load_config()["managed_processes"] == {}
            assert not mgr.stop_worker("w1")   # already gone
        finally:
            for p in procs:
                p.kill()

    def test_unknown_host_raises(self, tmp_config, monkeypatch):
        procs = []
        try:
            mgr = self._manager_with_fake_launch(tmp_config, monkeypatch, procs)
            with pytest.raises(ProcessError, match="no configured host"):
                mgr.launch_worker("nope")
        finally:
            for p in procs:
                p.kill()

    def test_local_worker_refused_where_this_process_holds_the_chips(
            self, tmp_config, monkeypatch):
        """One process per chip: on a host whose TPU this process has
        opened, a locally launched controller could never claim a device
        — refused with the reason, before anything is spawned."""
        import types

        import jax

        procs = []
        mgr = self._manager_with_fake_launch(tmp_config, monkeypatch, procs)
        chip = types.SimpleNamespace(platform="tpu",
                                     device_kind="TPU v5 lite")
        monkeypatch.setattr(jax, "devices", lambda *a: [chip] * 4)
        with pytest.raises(ProcessError, match="a chip belongs to one "
                                               "process"):
            mgr.launch_worker("w1")
        assert procs == [] and mgr.get_managed_workers() == {}

    def test_restore_and_reap(self, tmp_config, monkeypatch):
        """PID-only restore: alive PIDs restored, dead reaped (reference
        persistence.py:11-29)."""
        from comfyui_distributed_tpu.utils import config as config_mod

        live = subprocess.Popen([sys.executable, "-c", "import time; time.sleep(30)"])
        try:
            config_mod.update_config(lambda c: c.update(managed_processes={
                "alive": {"pid": live.pid, "log": ""},
                "dead": {"pid": 99999999, "log": ""},
            }))
            mgr = WorkerProcessManager()
            workers = mgr.get_managed_workers()
            assert "alive" in workers and "dead" not in workers
            # dead entry scrubbed from config too
            assert "dead" not in config_mod.load_config()["managed_processes"]
        finally:
            live.kill()
