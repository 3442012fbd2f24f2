"""Batched serving example: prefill + decode with KV caches across three
model families (dense GQA, MoE, hybrid SSM).

    PYTHONPATH=src python examples/serve_batched.py
"""
import os
import subprocess
import sys

from repro.launch.cache import enable_compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env["REPRO_DEVICES"] = "4"
    # the child processes share this run's compile cache directory
    env["JAX_COMPILATION_CACHE_DIR"] = enable_compile_cache()
    for arch in ("smollm-360m", "olmoe-1b-7b", "zamba2-7b"):
        cmd = [sys.executable, "-m", "repro.launch.serve", "--arch", arch,
               "--smoke", "--batch", "2", "--prompt-len", "16",
               "--gen", "6"]
        print("+", " ".join(cmd))
        subprocess.run(cmd, check=True, env=env)


if __name__ == "__main__":
    main()
