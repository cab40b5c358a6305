"""Job submission tests (reference: ``dashboard/modules/job/tests`` themes:
submit/status/logs/stop/list, entrypoint attaching back to the cluster)."""

import os
import sys
import tempfile
import time

import pytest

import ray_tpu
from conftest import REPO_ROOT
from ray_tpu import job


def test_submit_success_and_logs(ray_start_regular):
    jid = job.submit_job(f"{sys.executable} -c \"print('hello from job')\"")
    assert job.wait_job(jid, timeout=120) == job.SUCCEEDED
    assert "hello from job" in job.get_job_logs(jid)
    jobs = job.list_jobs()
    assert any(j["job_id"] == jid and j["status"] == job.SUCCEEDED for j in jobs)


def test_failed_job(ray_start_regular):
    jid = job.submit_job(f"{sys.executable} -c \"import sys; print('boom'); sys.exit(3)\"")
    assert job.wait_job(jid, timeout=120) == job.FAILED
    logs = job.get_job_logs(jid)
    assert "boom" in logs and "exit code 3" in logs


def test_stop_running_job(ray_start_regular):
    jid = job.submit_job(f"{sys.executable} -c \"import time; time.sleep(60)\"")
    deadline = time.time() + 30
    while job.get_job_status(jid) == job.PENDING and time.time() < deadline:
        time.sleep(0.1)
    assert job.stop_job(jid)
    assert job.wait_job(jid, timeout=60) == job.STOPPED


def test_env_vars_and_working_dir(ray_start_regular, tmp_path):
    jid = job.submit_job(
        f"{sys.executable} -c \"import os; print('V=' + os.environ['MY_JOB_VAR'], 'D=' + os.getcwd())\"",
        env_vars={"MY_JOB_VAR": "42"},
        working_dir=str(tmp_path),
    )
    assert job.wait_job(jid, timeout=120) == job.SUCCEEDED
    logs = job.get_job_logs(jid)
    assert "V=42" in logs
    assert f"D={tmp_path}" in logs


def test_entrypoint_attaches_to_cluster(ray_start_regular):
    """With a TCP listener up, the job's subprocess gets RAY_TPU_ADDRESS and
    can drive the SAME cluster that runs it."""
    from ray_tpu._private.runtime import get_ctx

    get_ctx().head.listen_tcp("127.0.0.1", 0)
    script = (
        "import os, ray_tpu\n"
        "ray_tpu.init(address=os.environ['RAY_TPU_ADDRESS'])\n"
        "@ray_tpu.remote\n"
        "def f(x):\n"
        "    return x + 1\n"
        "print('RESULT', ray_tpu.get(f.remote(41), timeout=60))\n"
        "ray_tpu.shutdown()\n"
    )
    path = tempfile.mktemp(suffix=".py")
    with open(path, "w") as f:
        f.write(script)
    env_path = REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", "")
    jid = job.submit_job(
        f"{sys.executable} {path}", env_vars={"PYTHONPATH": env_path}
    )
    assert job.wait_job(jid, timeout=180) == job.SUCCEEDED, job.get_job_logs(jid)
    assert "RESULT 42" in job.get_job_logs(jid)
