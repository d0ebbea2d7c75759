"""Run every dry-run cell in an isolated subprocess (OOM/crash resilient)."""
import itertools, os, subprocess, sys

ARCHS = ["deepseek-7b", "jamba-1.5-large-398b", "llama-3.2-vision-90b",
         "mamba2-370m", "mistral-nemo-12b", "moonlight-16b-a3b",
         "olmo-1b", "qwen1.5-110b", "qwen3-moe-30b-a3b",
         "seamless-m4t-large-v2"]
SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]

def main():
    force = "--force" in sys.argv
    for arch, shape, mp in itertools.product(ARCHS, SHAPES, ("sp", "mp")):
        tag = f"{arch}.{shape}.{mp}"
        path = f"benchmarks/artifacts/dryrun/{tag}.json"
        if not force and os.path.exists(path):
            print("have", tag, flush=True)
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", arch, "--shape", shape]
        if mp == "mp":
            cmd.append("--multi-pod")
        r = subprocess.run(cmd, env={**os.environ, "PYTHONPATH": "src"},
                           capture_output=True, text=True)
        status = r.stdout.strip().splitlines()[-1] if r.stdout.strip() else f"rc={r.returncode}"
        print(status, flush=True)

if __name__ == "__main__":
    main()
