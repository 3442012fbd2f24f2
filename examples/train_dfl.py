"""End-to-end DFL training driver example (deliverable b).

Runs the FULL stack: model zoo -> worker-stacked sharding -> masked-tau
local SGD -> matching-wise gossip collectives -> FedHP controller ->
checkpointing, on an 8-device host-platform mesh. Includes a
kill-and-resume leg exercising elastic restore.

    PYTHONPATH=src python examples/train_dfl.py

(At pod scale the same driver runs with --production; see
src/repro/launch/train.py.)
"""
import os
import subprocess
import sys
import tempfile

from repro.launch.cache import enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run(args, env):
    cmd = [sys.executable, "-m", "repro.launch.train"] + args
    print("+", " ".join(cmd))
    subprocess.run(cmd, check=True, env=env)


def main():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["REPRO_DEVICES"] = "8"
    # the child processes share this run's compile cache directory
    env["JAX_COMPILATION_CACHE_DIR"] = enable_compile_cache()
    with tempfile.TemporaryDirectory() as ckdir:
        # leg 1: 6 rounds with checkpoints every 3
        run(["--arch", "smollm-360m", "--smoke", "--steps", "6",
             "--workers", "4", "--checkpoint-dir", ckdir,
             "--checkpoint-every", "3"], env)
        # leg 2: resume from the checkpoint and continue to 10
        run(["--arch", "smollm-360m", "--smoke", "--steps", "10",
             "--workers", "4", "--checkpoint-dir", ckdir, "--resume"], env)
    print("train + checkpoint + elastic resume: OK")


if __name__ == "__main__":
    main()
