#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tony-tpu still starts on the chip.

    python3 chip_smoke.py               # one chip: the contract run
    python3 chip_smoke.py --four-chips  # the two in-process multi-chip paths
    python3 chip_smoke.py --rehearse    # CPU, toy widths: control flow only

Drives the main path once through the entry points a user calls, at
Llama-3.2-1B's published widths, with weights and prompts made from a seed:

  device    jax finds a TPU and one bf16 matmul runs on it
  kernels   flash fwd/bwd and flash-decode, compiled, vs the reference
            (head_dim 128 and 64); then a second run of the same child
            against the same compile cache (cold vs warm seconds)
  ckpt      an HF-format Llama-3.2-1B checkpoint written with transformers
  serve     `tony-tpu serve --hf-checkpoint`, a few /generate requests
  generate  the lock-step generate() path on the same checkpoint and
            prompts: greedy tokens must equal the server's
  train     `tony-tpu local --command "python -m tony_tpu.examples.lm_train"`
            through client -> driver -> executor -> child, depth cut to fit
            one chip, one checkpoint save
  restore   a second orchestrated job that resumes from that checkpoint

This parent process NEVER imports jax: a chip belongs to one process at a
time, so every phase is a child started one after another, each gone before
the next starts. Before each phase one line {"phase", "cmd"}; after it one
line with seconds, exit code and what it established. A failed phase prints
the end of its child's logs and the script exits non-zero at once. The last
line of a passing run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the children reported it.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import shlex
import shutil
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chiprun_out" / "chip_smoke"   # logs and results: small, kept
WORK = ROOT / ".chip_smoke_work"            # weights, checkpoints: removed
PY = sys.executable
TOTAL_BUDGET_S = 1100.0                     # the contract allows 1200
TAIL_LINES = 60

# Llama-3.2-1B as published (meta-llama/Llama-3.2-1B config.json)
LLAMA_3_2_1B = dict(
    hidden_size=2048, num_hidden_layers=16, num_attention_heads=32,
    num_key_value_heads=8, head_dim=64, intermediate_size=8192,
    vocab_size=128256, tie_word_embeddings=True, rope_theta=500000.0,
    rope_scaling=dict(factor=32.0, high_freq_factor=4.0, low_freq_factor=1.0,
                      original_max_position_embeddings=8192,
                      rope_type="llama3"),
    max_position_embeddings=131072, rms_norm_eps=1e-5, hidden_act="silu",
    attention_bias=False, mlp_bias=False, initializer_range=0.02,
    bos_token_id=128000, eos_token_id=128001,
)
# the same graph features (GQA, tied embeddings, llama3 rope) at toy widths
TOY_LLAMA = dict(
    LLAMA_3_2_1B, hidden_size=128, num_hidden_layers=2,
    num_attention_heads=8, num_key_value_heads=4, head_dim=16,
    intermediate_size=256, vocab_size=512, max_position_embeddings=2048,
    rope_scaling=dict(LLAMA_3_2_1B["rope_scaling"],
                      original_max_position_embeddings=64),
    bos_token_id=1, eos_token_id=2,
)

# what each mode runs. Widths are the published ones; only depth, batch,
# the number of requests and the number of steps are this script's to cut.
REAL = dict(
    llama=LLAMA_3_2_1B, expect="tpu",
    serve=dict(slots=4, max_len=1024, block_size=16, prefill_chunk=128),
    # (prompt length, new tokens): the first crosses a prefill chunk and
    # three decode blocks; the rest go in concurrently
    requests=[(200, 48), (7, 16), (33, 24), (64, 16)],
    train=dict(layers=2, batch=2, seq=2048, steps=60),
    train4=dict(layers=16, batch=4, seq=2048, steps=41),
    restore_steps=20,
    kernel_len=1024, decode_len=4096,
    # rows, width, vocabulary of the blockwise cross-entropy case: the
    # benchmark's train cell (batch 2 x 4096 at Mistral-7B v0.3 widths)
    ce_shape=(8192, 4096, 32768),
)
TOY = dict(
    llama=TOY_LLAMA, expect="cpu",
    serve=dict(slots=4, max_len=128, block_size=4, prefill_chunk=8),
    requests=[(20, 12), (3, 5), (9, 6), (5, 4)],
    train=dict(layers=2, batch=4, seq=32, steps=60),
    train4=dict(layers=2, batch=4, seq=32, steps=41),
    restore_steps=20,
    kernel_len=256, decode_len=512,
    ce_shape=(96, 32, 200),
)


class PhaseFailed(Exception):
    pass


def say(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def tail(path: Path, n: int = TAIL_LINES) -> str:
    try:
        lines = path.read_text(errors="replace").splitlines()
    except OSError as e:
        return f"<unreadable: {e}>"
    return "\n".join(lines[-n:])


def echo_logs(paths) -> None:
    for p in paths:
        p = Path(p)
        if p.is_file() and p.stat().st_size:
            print(f"----- last {TAIL_LINES} lines of {p} -----", flush=True)
            print(tail(p), flush=True)


def descendants(pid: int) -> list[int]:
    """Every live descendant of pid, by /proc ppid links (the executor and
    the driver start new sessions, so a process-group kill misses them)."""
    kids: dict[int, list[int]] = {}
    for entry in os.listdir("/proc"):
        if not entry.isdigit():
            continue
        try:
            stat = Path(f"/proc/{entry}/stat").read_text()
            ppid = int(stat.rsplit(")", 1)[1].split()[1])
        except (OSError, ValueError, IndexError):
            continue
        kids.setdefault(ppid, []).append(int(entry))
    out, todo = [], [pid]
    while todo:
        for k in kids.get(todo.pop(), []):
            out.append(k)
            todo.append(k)
    return out


def kill_tree(proc: subprocess.Popen) -> None:
    pids = descendants(proc.pid) + [proc.pid]
    for sig in (signal.SIGTERM, signal.SIGKILL):
        for pid in pids:
            try:
                os.kill(pid, sig)
            except OSError:
                pass
        try:
            proc.wait(timeout=5)
        except subprocess.TimeoutExpired:
            continue
        if not any(Path(f"/proc/{p}").exists() for p in pids[:-1]):
            break


class Runner:
    """Runs phases one after another and keeps what each established."""

    def __init__(self, out: Path, work: Path,
                 budget_s: float = TOTAL_BUDGET_S):
        self.out = out          # logs and results: small, kept
        self.work = work        # weights, checkpoints: removed at the end
        self.deadline = time.monotonic() + budget_s
        self.results: dict[str, dict] = {}
        self.devices: dict[str, dict] = {}     # phase -> device it reported

    def child_env(self, extra: dict | None = None) -> dict:
        env = dict(os.environ)
        env["PYTHONPATH"] = str(ROOT) + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        env["PYTHONUNBUFFERED"] = "1"
        # small kernels compile in under JAX's one-second threshold; the
        # warm rerun must be able to hit them. The directory itself is
        # placed by tony_tpu/utils/jaxenv.py (JAX_COMPILATION_CACHE_DIR
        # if set, else the checkout's own), never by this script.
        env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
        env.setdefault("TPU_LOG_DIR", "disabled")
        # transformers otherwise probes for TensorFlow and Flax at import:
        # 30 s here and 58 s on the chip machine against 8 s without
        env.update(USE_TORCH="1", USE_TF="0", USE_FLAX="0")
        env.update(extra or {})
        return env

    def timeout_for(self, own: float) -> float:
        left = self.deadline - time.monotonic()
        if left <= 5:
            raise PhaseFailed("the run's own time budget is spent")
        return min(own, left)

    def start(self, name: str, cmd: list[str]) -> float:
        say({"phase": name, "cmd": " ".join(shlex.quote(c) for c in cmd)})
        return time.monotonic()

    def finish(self, name: str, t0: float, rc, established: dict) -> None:
        rec = {"phase": name, "seconds": round(time.monotonic() - t0, 1),
               "rc": rc, "established": established}
        self.results[name] = rec
        if isinstance(established.get("device"), dict):
            self.devices[name] = established["device"]
        say(rec)

    def fail(self, name: str, t0: float, rc, why: str, logs) -> None:
        say({"phase": name, "seconds": round(time.monotonic() - t0, 1),
             "rc": rc, "failed": why})
        echo_logs(logs)
        raise PhaseFailed(f"{name}: {why}")

    @contextlib.contextmanager
    def child(self, name: str, cmd: list[str], env: dict | None = None):
        """The phase's child, its output in <out>/<name>.std{out,err}.
        -> (process, [its logs]); whatever it leaves running is killed on
        the way out, so the next phase finds the chip free."""
        logs = [self.out / f"{name}.stdout", self.out / f"{name}.stderr"]
        with open(logs[0], "wb") as fo, open(logs[1], "wb") as fe:
            proc = subprocess.Popen(cmd, stdout=fo, stderr=fe, cwd=ROOT,
                                    env=self.child_env(env),
                                    start_new_session=True)
        try:
            yield proc, logs
        finally:
            if proc.poll() is None or descendants(proc.pid):
                kill_tree(proc)

    def run(self, name: str, cmd: list[str], *, timeout: float, check,
            env: dict | None = None) -> dict:
        """One child to completion. ``check(stdout) -> dict`` says what the
        phase established and raises on anything that is not right."""
        limit = self.timeout_for(timeout)
        t0 = self.start(name, cmd)
        with self.child(name, cmd, env) as (proc, logs):
            try:
                rc = proc.wait(timeout=limit)
            except subprocess.TimeoutExpired:
                self.fail(name, t0, None,
                          f"no exit within {limit:.0f}s (killed)", logs)
        if rc != 0:
            self.fail(name, t0, rc, f"exit code {rc}", logs)
        try:
            established = check(logs[0].read_text(errors="replace"))
        except Exception as e:  # the check IS the phase: report and stop
            self.fail(name, t0, rc, f"check failed: {e!r}", logs)
        self.finish(name, t0, rc, established)
        return established


def last_json_line(text: str) -> dict:
    for line in reversed(text.splitlines()):
        line = line.strip()
        if line.startswith("{") and line.endswith("}"):
            try:
                return json.loads(line)
            except ValueError:
                continue
    raise ValueError("no JSON line in the child's output")


def require_platform(device: dict, expect: str, count: int | None = None):
    if device.get("platform") != expect:
        raise ValueError(f"ran on {device}, not on platform {expect!r}")
    if count is not None and device.get("count") != count:
        raise ValueError(f"found {device.get('count')} devices, need {count}")
    return {k: device.get(k)
            for k in ("platform", "kind", "count", "bytes_in_use")}


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def http(url: str, payload: dict | None = None, timeout: float = 30.0):
    data = None if payload is None else json.dumps(payload).encode()
    with urllib.request.urlopen(
            urllib.request.Request(url, data=data), timeout=timeout) as r:
        return r.read().decode()


def prompts_from_seed(seed: int, vocab: int, requests) -> list[list[int]]:
    import random

    rng = random.Random(seed)
    return [[rng.randrange(3, vocab) for _ in range(n)] for n, _ in requests]


# --------------------------------------------------------------- the phases
def phase_device(r: Runner, mode: dict) -> None:
    def check(out):
        rep = last_json_line(out)
        require_platform(rep["device"], mode["expect"], 1)
        if not rep["matmul_finite"]:
            raise ValueError("the bf16 matmul gave non-finite values")
        return rep
    r.run("device", [PY, __file__, "--child", "device"], timeout=180,
          check=check)


def phase_kernels(r: Runner, mode: dict) -> None:
    def check(out):
        rep = last_json_line(out)
        require_platform(rep["device"], mode["expect"])
        bad = [c["case"] for c in rep["cases"] if not c["ok"]]
        if bad:
            raise ValueError(f"kernel differs from the reference: {bad}")
        pallas = [c for c in rep["cases"] if c["pallas"]]
        if mode["expect"] == "tpu":
            interp = [c["case"] for c in pallas if not c["compiled"]]
            if interp:
                raise ValueError(f"not compiled (no tpu_custom_call): {interp}")
        return {"device": rep["device"], "seconds": rep["seconds"],
                "cache": rep["cache"],
                "compiled": all(c["compiled"] for c in pallas),
                "max_abs_err": {c["case"]: float(f"{c['max_abs_err']:.3g}")
                                for c in rep["cases"]}}
    cmd = [PY, __file__, "--child", "kernels",
           "--kernel-len", str(mode["kernel_len"]),
           "--decode-len", str(mode["decode_len"]),
           "--ce-shape", ",".join(map(str, mode["ce_shape"]))]
    cold = r.run("kernels", cmd, timeout=400, check=check)

    def check_warm(out):
        rep = check(out)
        if rep["cache"]["hits"] < 1:
            raise ValueError(
                f"second run, same cache dir, no cache hit: {rep['cache']}")
        return {"device": rep["device"], "cache": rep["cache"],
                "first_run": {"seconds": cold["seconds"],
                              "cache": cold["cache"]},
                "second_run_seconds": rep["seconds"]}
    r.run("kernels_warm", cmd, timeout=300, check=check_warm)


def phase_ckpt(r: Runner, mode: dict, seed: int) -> Path:
    ckpt = r.work / "llama_ckpt"
    cfg_path = r.work / "llama_config.json"
    cfg_path.write_text(json.dumps(mode["llama"]))

    def check(out):
        rep = last_json_line(out)
        if not (ckpt / "config.json").is_file():
            raise ValueError("no config.json was written")
        return rep
    r.run("ckpt", [PY, __file__, "--child", "ckpt", "--config", str(cfg_path),
                   "--dir", str(ckpt), "--seed", str(seed)],
          # torch on the host CPU; never needs the chip
          env={"JAX_PLATFORMS": "cpu"}, timeout=400, check=check)
    return ckpt


def phase_serve(r: Runner, mode: dict, ckpt: Path, prompts, *, name="serve",
                mesh: str = "", count: int | None = 1,
                compare_to: dict | None = None) -> dict:
    """Start the server, wait for /healthz, send the requests (the long one
    first and alone, the rest concurrently), read /stats and /metrics, stop
    it with SIGTERM and require a clean exit. -> {"tokens", "top"}: the
    generated tokens and, per position, the server's own top-k logprobs.
    ``compare_to``: another server's answer for the same prompts, held to
    the same rule as generate() (compare_with_resync) while this one is up."""
    port = free_port()
    s = mode["serve"]
    cmd = [PY, "-m", "tony_tpu.cli.main", "serve",
           "--hf-checkpoint", str(ckpt), "--dtype", "bfloat16",
           "--port", str(port), "--host", "127.0.0.1",
           "--slots", str(s["slots"]), "--max-len", str(s["max_len"]),
           "--block-size", str(s["block_size"]),
           "--prefill-chunk", str(s["prefill_chunk"]),
           "--drain-timeout-s", "20"]
    if mesh:
        cmd += ["--mesh", mesh]
    limit = r.timeout_for(700)
    t0 = r.start(name, cmd)
    base = f"http://127.0.0.1:{port}"
    with r.child(name, cmd) as (proc, logs):
        t_end = t0 + limit
        healthy_s = None
        while time.monotonic() < t_end:
            if proc.poll() is not None:
                r.fail(name, t0, proc.returncode,
                       "the server exited before it was healthy", logs)
            try:
                if json.loads(http(base + "/healthz", timeout=5))["healthy"]:
                    healthy_s = round(time.monotonic() - t0, 1)
                    break
            except (OSError, ValueError, urllib.error.URLError):
                pass
            time.sleep(1.0)
        if healthy_s is None:
            r.fail(name, t0, None, f"/healthz not ok within {limit:.0f}s",
                   logs)
        stats0 = json.loads(http(base + "/stats"))
        try:
            device = require_platform(stats0["device"], mode["expect"], count)
        except (KeyError, ValueError) as e:
            r.fail(name, t0, None, f"/stats: {e!r}", logs)

        tokens: list = [None] * len(prompts)
        top: list = [None] * len(prompts)
        errors: list[str] = []
        timings: list = [None] * len(prompts)

        def ask(i: int) -> None:
            t = time.monotonic()
            try:
                body = json.loads(http(
                    base + "/generate",
                    {"prompt": prompts[i], "logprobs": TOP_K,
                     "max_new_tokens": mode["requests"][i][1]},
                    timeout=max(10.0, t_end - time.monotonic())))
                tokens[i] = body["tokens"]
                top[i] = [dict(zip(*e["top"])) for e in body["logprobs"]]
                if len(body["tokens"]) != mode["requests"][i][1]:
                    errors.append(f"request {i}: {len(body['tokens'])} tokens"
                                  f", finish {body.get('finish_reason')}")
            except Exception as e:
                errors.append(f"request {i}: {e!r}")
            timings[i] = round(time.monotonic() - t, 2)

        ask(0)                          # first: pays the compiles, alone
        threads = [threading.Thread(target=ask, args=(i,))
                   for i in range(1, len(prompts))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(max(1.0, t_end - time.monotonic()))
        if errors or any(t is None for t in tokens):
            r.fail(name, t0, None, f"requests failed: {errors or 'timeout'}",
                   logs)
        agreement = None
        if compare_to is not None:
            def resume(prompt, n):
                return json.loads(http(
                    base + "/generate",
                    {"prompt": prompt, "max_new_tokens": n},
                    timeout=max(10.0, t_end - time.monotonic())))["tokens"]
            agreement = compare_with_resync(resume, prompts, compare_to)
            if agreement["unexplained"]:
                r.fail(name, t0, None, "tokens differ from the one-chip "
                       f"server's where it saw no near-tie: {agreement}",
                       logs)
        stats = json.loads(http(base + "/stats"))
        metrics = http(base + "/metrics")
        (r.out / f"{name}.stats.json").write_text(json.dumps(stats, indent=1))
        (r.out / f"{name}.metrics.txt").write_text(metrics)
        if "serving_" not in metrics:
            r.fail(name, t0, None, "/metrics has no serving_ family", logs)
        proc.send_signal(signal.SIGTERM)
        try:
            rc = proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            r.fail(name, t0, None, "no exit within 60s of SIGTERM", logs)
        if rc != 0:
            r.fail(name, t0, rc, f"exit code {rc} after SIGTERM", logs)
    r.finish(name, t0, rc, {
        "device": device, "healthy_after_s": healthy_s,
        "requests": [{"prompt_len": len(p), "new": len(t), "seconds": s_}
                     for p, t, s_ in zip(prompts, tokens, timings)],
        "compile": stats.get("compile"),
        "bytes_in_use": stats["device"].get("bytes_in_use"),
        "clean_shutdown": True,
        **({"vs_one_chip_server": {
            "identical": not agreement["ties"],
            "tokens_compared": agreement["tokens_compared"],
            "near_ties": agreement["ties"]}} if agreement else {})})
    return {"tokens": tokens, "top": top}


# Greedy decoding of RANDOM weights is ill-conditioned: the 128256 logits
# are near-flat, the top two are often closer than bfloat16 rounding, and
# two correct programs (chunked prefill + ring cache + whatever shares the
# batch, vs one prefill + lock-step cache) then pick different winners — on
# the v5e about 3 tokens in 100 — after which free-running sequences share
# nothing. So the server is asked for its own top-k at every position, and a
# difference is a NEAR-TIE only where the other path's token is in that
# top-k within TIE_EPS of the server's choice; the other path then resumes
# from the server's history. A server that computes something else picks
# tokens nowhere near the other path's among 128256, and fails at once.
TOP_K = 4
TIE_EPS = 0.2           # nats, between the server's choice and the other's
MAX_NEAR_TIES = 12      # each costs the other path one more run (~8 s)


def compare_with_resync(generate, prompts, served: dict) -> dict:
    """``generate(prompt, n) -> tokens`` against the server's answer
    ({"tokens", "top"}, see phase_serve). Every difference must be a
    near-tie by the server's own logprobs; anything else is
    ``unexplained`` and ends the comparison."""
    ties, unexplained, compared = [], [], 0
    for i, (prompt, want) in enumerate(zip(prompts, served["tokens"])):
        done = 0                        # server tokens accounted for
        while done < len(want) and not unexplained:
            got = generate(prompt + want[:done], len(want) - done)
            rest = want[done:]
            same = next((j for j, (a, b) in enumerate(zip(got, rest))
                         if a != b), min(len(got), len(rest)))
            compared += min(same + 1, len(rest))
            done += same
            if done == len(want):
                break
            other = got[same] if same < len(got) else None
            seen = {int(k): v for k, v in served["top"][i][done].items()}
            gap = (seen[want[done]] - seen[other]
                   if other in seen and want[done] in seen else None)
            diff = {"prompt": i, "index": done, "server": want[done],
                    "other": other, "logprob_gap": gap}
            if (gap is not None and abs(gap) <= TIE_EPS
                    and len(ties) < MAX_NEAR_TIES):
                ties.append(diff)
            else:
                unexplained.append(diff)
            done += 1                   # resume from the server's history
    return {"tokens_compared": compared, "ties": ties,
            "unexplained": unexplained}


def phase_generate(r: Runner, mode: dict, ckpt: Path, prompts,
                   served: dict) -> None:
    spec = r.work / "generate_spec.json"
    spec.write_text(json.dumps({
        "ckpt": str(ckpt), "prompts": prompts, "served": served}))

    def check(out):
        rep = last_json_line(out)
        require_platform(rep["device"], mode["expect"])
        if rep["unexplained"]:
            raise ValueError(
                f"generate() and the server differ where the server saw no "
                f"near-tie: {rep['unexplained']}; near-ties so far "
                f"{rep['ties']}")
        return {"device": rep["device"], "prompts": len(prompts),
                "tokens_compared": rep["tokens_compared"],
                "identical_to_server": not rep["ties"],
                "near_ties": rep["ties"], "seconds_each": rep["seconds"]}
    r.run("generate", [PY, __file__, "--child", "generate",
                       "--spec", str(spec)], timeout=700, check=check)


def libtpu_holders(seen: dict) -> None:
    """Note every process that has libtpu mapped (pid -> command line)."""
    for entry in os.listdir("/proc"):
        if not entry.isdigit() or int(entry) == os.getpid():
            continue
        try:
            cmd = Path(f"/proc/{entry}/cmdline").read_bytes().replace(
                b"\0", b" ").decode(errors="replace").strip()
            if not cmd or "tony_tpu" not in cmd:
                continue
            rec = seen.setdefault(int(entry), {"cmd": cmd, "libtpu": False})
            if not rec["libtpu"] and "libtpu" in Path(
                    f"/proc/{entry}/maps").read_text(errors="replace"):
                rec["libtpu"] = True
        except OSError:
            continue


def train_cmd(mode: dict, t: dict, steps: int, ckpt_dir: Path,
              mesh: str = "") -> str:
    c = mode["llama"]
    cmd = [PY, "-m", "tony_tpu.examples.lm_train",
           "--d-model", str(c["hidden_size"]),
           "--n-heads", str(c["num_attention_heads"]),
           "--d-ff", str(c["intermediate_size"]),
           "--vocab", str(c["vocab_size"]),
           "--n-layers", str(t["layers"]), "--seq-len", str(t["seq"]),
           "--batch-size", str(t["batch"]), "--steps", str(steps),
           "--dtype", "bfloat16", "--checkpoint-every", "100000"]
    if ckpt_dir:
        cmd += ["--checkpoint-dir", str(ckpt_dir)]
    if mesh:
        cmd += ["--mesh", mesh]
    return " ".join(shlex.quote(x) for x in cmd)


def parse_train_log(text: str) -> dict:
    losses = []
    for line in text.splitlines():
        if line.startswith("step ") and ": loss " in line:
            losses.append((int(line.split()[1].rstrip(":")),
                           float(line.split(": loss ")[1].split()[0])))
    final = last_json_line(text)
    return {"losses": losses, "final": final,
            "resumed": [l for l in text.splitlines()
                        if l.startswith("resumed from checkpoint")]}


def check_losses(losses, final_loss: float, below: float | None = None):
    import math

    values = [v for _, v in losses] + [final_loss]
    if not all(math.isfinite(v) for v in values):
        raise ValueError(f"non-finite loss: {values}")
    first = values[0] if below is None else below
    if not values[-1] < first:
        raise ValueError(f"loss did not fall: {values} (from {first})")


def phase_train_job(r: Runner, mode: dict, name: str, steps: int,
                    ckpt_dir: Path, *, first: bool,
                    below: float | None = None) -> dict:
    """One orchestrated job: client -> driver -> executor -> lm_train.
    The ``first`` job must get its step log to the driver and show which
    attention path its step compiled; the second must resume from the
    first's save and stay ``below`` the loss the first started from."""
    staging, hist = r.work / f"{name}_staging", r.work / f"{name}_hist"
    dump = r.work / f"{name}_hlo"
    t = mode["train"]
    cmd = [PY, "-m", "tony_tpu.cli.main", "local", "--instances", "1",
           "--command", train_cmd(mode, t, steps, ckpt_dir),
           "-D", f"tony.staging.dir={staging}",
           "-D", f"tony.history.intermediate={hist}",
           "-D", "tony.task.metrics-interval-ms=500",
           # a standby would initialise the backend beside the child that
           # needs the chip (tony_tpu/warmpool.py): pool off on one chip
           "-D", "tony.warmpool.size=0"]
    limit = r.timeout_for(700)
    t0 = r.start(name, cmd)
    # the step program's text, dumped by the child's own compiler, is what
    # says which attention path the train step really compiled. A program
    # served from the persistent cache is never compiled, hence never
    # dumped: the first job compiles its own; the second may hit the cache
    env = {"XLA_FLAGS": (
        os.environ.get("XLA_FLAGS", "") + f" --xla_dump_to={dump}"
        " --xla_dump_hlo_module_re=^jit_step$ --xla_dump_hlo_as_text"
    ).strip(), "JAX_ENABLE_COMPILATION_CACHE": "false"} if first else {}
    seen: dict = {}
    with r.child(name, cmd, env) as (proc, cli_logs):
        while proc.poll() is None and time.monotonic() - t0 < limit:
            libtpu_holders(seen)
            time.sleep(0.25)
        rc = proc.poll()
    job_logs = [p for p in sorted(staging.glob("*/logs/*")) + sorted(
        staging.glob("*/driver.log")) if p.is_file()]
    kept = r.out / f"{name}_job"
    kept.mkdir(exist_ok=True)
    for p in job_logs:                  # r.work is removed at the end
        if p.stat().st_size < 4 << 20:
            shutil.copy(p, kept / p.name)
    logs = [*cli_logs, *job_logs]
    if rc is None:
        r.fail(name, t0, None, f"no exit within {limit:.0f}s (killed)", logs)
    if rc != 0:
        r.fail(name, t0, rc, f"the CLI exited {rc}", logs)
    try:
        child_out = next(iter(staging.glob("*/logs/worker_0.stdout")))
        got = parse_train_log(child_out.read_text(errors="replace"))
        final = got["final"]
        device = require_platform(final["device"], mode["expect"], 1)
        check_losses(got["losses"], final["final_loss"], below)
        # the driver's record of what the executor's monitor pushed
        finished = [json.loads(l) for p in hist.rglob("*.jhist")
                    for l in p.read_text().splitlines()
                    if '"TASK_FINISHED"' in l]
        pushed = {m["name"]: m["value"] for ev in finished
                  for m in ev["payload"].get("metrics", [])}
        if "max_memory_rss_mb" not in pushed:
            raise ValueError("the executor's monitor pushed nothing")
        if first and not pushed.get("max_train_step", 0) > 0:
            raise ValueError(
                f"train_step never reached the driver: {sorted(pushed)}")
        module = lambda v: v["cmd"].split(" -m ")[-1].split()[0]
        holders = sorted({module(v) for v in seen.values() if v["libtpu"]})
        others = sorted({module(v) for v in seen.values()
                         if not v["libtpu"]})
        if mode["expect"] == "tpu":
            if holders != ["tony_tpu.examples.lm_train"]:
                raise ValueError(f"libtpu was loaded by {holders}")
            if "tony_tpu.executor" not in others:
                raise ValueError(f"never saw the executor: {others}")
        texts = [p.read_text(errors="replace")
                 for p in dump.glob("*jit_step*after_optimizations.txt")]
        if first and not texts:
            raise ValueError(f"the child dumped no jit_step program: {dump}")
        attention = (None if not first
                     else "pallas flash kernel (tpu_custom_call)"
                     if any("tpu_custom_call" in x for x in texts)
                     else "reference einsum (no tpu_custom_call)")
        if not first and not got["resumed"]:
            raise ValueError("the second job did not resume from the save")
    except Exception as e:
        r.fail(name, t0, rc, f"check failed: {e!r}", logs)
    est = {"device": device, "losses": got["losses"],
           "final_loss": final["final_loss"], "n_params": final["n_params"],
           "resumed": got["resumed"],
           **({"attention_path": attention} if first else {}),
           "driver_saw": {k: pushed[k] for k in sorted(pushed)
                          if k.startswith(("max_train_step", "max_tpu_",
                                           "max_ckpt_step"))},
           "libtpu_loaded_by": holders, "without_libtpu": others,
           "bytes_in_use": final["device"].get("bytes_in_use")}
    r.finish(name, t0, rc, est)
    return est


def phase_train(r: Runner, mode: dict) -> None:
    c, t = mode["llama"], mode["train"]
    say({"note": "train phase vs the published config",
         "kept": {"d_model": c["hidden_size"], "d_ff": c["intermediate_size"],
                  "n_heads": c["num_attention_heads"],
                  "head_dim": c["head_dim"], "vocab": c["vocab_size"],
                  "seq_len": t["seq"], "activations": "bfloat16"},
         "cut": {"n_layers": f"{t['layers']} of {c['num_hidden_layers']}: "
                 "f32 parameters and Adam state of the full depth do not "
                 "fit one chip's 16 GB", "batch": t["batch"]},
         "differs": "lm_train has no flags for them: kv heads = heads "
                    "(published 8), untied unembedding (published tied), "
                    "rope theta 10000 without llama3 scaling"})
    ckpt_dir = r.work / "train_ckpt"
    first = phase_train_job(r, mode, "train", t["steps"], ckpt_dir,
                            first=True)
    phase_train_job(r, mode, "restore", mode["restore_steps"], ckpt_dir,
                    first=False, below=first["losses"][0][1])


def phase_train4(r: Runner, mode: dict) -> None:
    t = mode["train4"]
    cmd = shlex.split(train_cmd(mode, t, t["steps"], "", mesh="fsdp=4"))

    def check(out):
        got = parse_train_log(out)
        device = require_platform(got["final"]["device"], mode["expect"], 4)
        check_losses(got["losses"], got["final"]["final_loss"])
        held = device.get("bytes_in_use") or []
        if mode["expect"] == "tpu" and not (
                len(held) == 4 and all(b and b > 0 for b in held)):
            raise ValueError(f"not every device holds a share: {held}")
        return {"device": device, "losses": got["losses"],
                "final_loss": got["final"]["final_loss"],
                "n_params": got["final"]["n_params"],
                "mesh": got["final"]["mesh"], "bytes_in_use": held}
    r.run("train_fsdp4", cmd, timeout=900, check=check)


# -------------------------------------------------- children (these use jax)
def child_device() -> int:
    t0 = time.monotonic()
    import jax
    import jax.numpy as jnp

    from tony_tpu.utils.jaxenv import device_report, place_compile_cache

    cache = place_compile_cache()
    dev = device_report()
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    y = jax.jit(lambda a: a @ a)(x).block_until_ready()
    stats = jax.devices()[0].memory_stats()
    # can the chip's owner ask libtpu's monitoring SDK without blocking?
    # (the StepTimer does, once per record; the executor never does)
    probe: dict = {}

    def ask():
        from tony_tpu.metrics import sample_tpu_metrics

        t = time.monotonic()
        probe["sample"], probe["reason"] = sample_tpu_metrics(explain=True)
        probe["seconds"] = round(time.monotonic() - t, 2)

    th = threading.Thread(target=ask, daemon=True)
    th.start()
    th.join(20)
    say({"device": dev, "matmul_finite": bool(jnp.isfinite(y).all()),
         "memory_stats_answers": stats is not None,
         "owner_tpu_sample": probe or "blocked for more than 20 s",
         "compile_cache": cache, "jax": jax.__version__,
         "seconds": round(time.monotonic() - t0, 1)})
    sys.stdout.flush()
    os._exit(0)     # a blocked probe thread must not hold the exit


def child_ckpt(config: str, out_dir: str, seed: int) -> int:
    t0 = time.monotonic()
    import torch
    from transformers import LlamaConfig, LlamaForCausalLM

    t_import = time.monotonic() - t0
    torch.manual_seed(seed)
    cfg = LlamaConfig(**json.loads(Path(config).read_text()))
    model = LlamaForCausalLM(cfg).to(torch.bfloat16)
    n = sum(p.numel() for p in model.parameters())
    model.save_pretrained(out_dir, safe_serialization=True)
    say({"wrote": out_dir, "n_params": n, "dtype": "bfloat16", "seed": seed,
         "import_seconds": round(t_import, 1),
         "seconds": round(time.monotonic() - t0, 1)})
    return 0


def child_generate(spec_path: str) -> int:
    import functools

    from tony_tpu.examples import lm_generate
    from tony_tpu.models import hf_import

    # one load for all the runs of lm_generate.main in this process
    hf_import.load_hf = functools.cache(hf_import.load_hf)
    spec = json.loads(Path(spec_path).read_text())
    out = Path(spec_path).with_name("generate_out.json")
    seconds, devices = [], []

    def generate(prompt, n):
        t = time.monotonic()
        rc = lm_generate.main([
            "--hf-checkpoint", spec["ckpt"], "--dtype", "bfloat16",
            "--prompt", " ".join(map(str, prompt)), "--max-new", str(n),
            "--metrics-out", str(out)])
        if rc != 0:
            raise SystemExit(rc)
        seconds.append(round(time.monotonic() - t, 1))
        got = json.loads(out.read_text())
        devices.append(got["device"])
        return got["tokens"]

    rep = compare_with_resync(generate, spec["prompts"], spec["served"])
    say({"device": devices[-1], **rep, "seconds": seconds})
    return 0


def child_kernels(kernel_len: int, decode_len: int, ce_shape: tuple) -> int:
    t0 = time.monotonic()
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tony_tpu.utils.jaxenv import device_report, place_compile_cache

    cache_dir = place_compile_cache()
    counts = {"hits": 0, "misses": 0}

    def on_event(name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            counts["hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            counts["misses"] += 1
    jax.monitoring.register_event_listener(on_event)

    from tony_tpu.ops.attention import flash_attention_with_lse
    from tony_tpu.ops.decode_attention import flash_decode
    from tony_tpu.parallel import reference_attention

    on_tpu = jax.default_backend() == "tpu"
    f32 = jnp.float32
    cases = []

    def record(case, got, want, text, tol=3e-2, pallas=True):
        got = np.asarray(jnp.asarray(got, f32))
        want = np.asarray(jnp.asarray(want, f32))
        err = float(np.max(np.abs(got - want)))
        scale = float(np.max(np.abs(want)))
        cases.append({
            "case": case, "max_abs_err": err, "ref_max_abs": scale,
            "finite": bool(np.isfinite(got).all()),
            "compiled": "tpu_custom_call" in text, "pallas": pallas,
            "ok": bool(np.isfinite(got).all() and err <= tol * scale)})

    def reference(q, k, v, window):
        # [B, H, L, D] bf16 in; the plain einsum at f32, full precision
        with jax.default_matmul_precision("highest"):
            o = reference_attention(
                q.astype(f32).transpose(0, 2, 1, 3),
                k.astype(f32).transpose(0, 2, 1, 3),
                v.astype(f32).transpose(0, 2, 1, 3), causal=True,
                window=window)
        return o.transpose(0, 2, 1, 3)

    def decode_ref(q, ck, cv, length, window=0):
        with jax.default_matmul_precision("highest"):
            m = ck.shape[2]
            s = jnp.einsum("bhrd,bhmd->bhrm", q.astype(f32),
                           ck.astype(f32)) * ck.shape[-1] ** -0.5
            mask = jnp.arange(m) <= length
            if window:
                mask &= jnp.arange(m) > length - window
            s = jnp.where(mask[None, None, None], s, -1e30)
            return jnp.einsum("bhrm,bhmd->bhrd", jax.nn.softmax(s, -1),
                              cv.astype(f32))

    B, H, L = 2, 8, kernel_len
    for d in (128, 64):
        ks = jax.random.split(jax.random.PRNGKey(d), 4)
        q, k, v, g = (jax.random.normal(kk, (B, H, L, d), jnp.bfloat16)
                      for kk in ks)
        for window in (None, L // 4):
            tag = f"hd{d}" + (f"_window{window}" if window else "")

            def fwd(q, k, v, window=window):
                return flash_attention_with_lse(q, k, v, True, None,
                                                window)[0]

            def loss(q, k, v, window=window):
                return jnp.sum(fwd(q, k, v, window).astype(f32)
                               * g.astype(f32))

            def ref_loss(q, k, v, window=window):
                return jnp.sum(reference(q, k, v, window) * g.astype(f32))

            text = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
                q, k, v).as_text()
            out = jax.jit(fwd)(q, k, v)
            grads = jax.jit(jax.grad(loss, (0, 1, 2)))(q, k, v)
            want = jax.jit(lambda q, k, v: reference(q, k, v, window))(
                q, k, v)
            want_g = jax.jit(jax.grad(ref_loss, (0, 1, 2)))(
                q.astype(f32), k.astype(f32), v.astype(f32))
            record(f"flash_fwd_{tag}", out, want, text)
            for nm, a, b in zip(("dq", "dk", "dv"), grads, want_g):
                record(f"flash_bwd_{nm}_{tag}", a, b, text)

        # flash-decode: GQA as published (8 kv heads x 4), bf16 and int8
        # cache, the layer-indexed stack, a window
        kvh, rep, m = 8, 4, decode_len
        length = m - m // 4 - 3
        ks = jax.random.split(jax.random.PRNGKey(1000 + d), 3)
        qd = jax.random.normal(ks[0], (B, kvh, rep, d), jnp.bfloat16)
        ck = jax.random.normal(ks[1], (2, B, kvh, m, d), jnp.bfloat16)
        cv = jax.random.normal(ks[2], (2, B, kvh, m, d), jnp.bfloat16)
        interp = not on_tpu

        def dec(qd, ck, cv, **kw):
            return flash_decode(qd, ck, cv, jnp.int32(length),
                                interpret=interp, **kw)

        text = jax.jit(lambda a, b, c: dec(a, b, c)).lower(
            qd, ck[0], cv[0]).as_text()
        record(f"flash_decode_hd{d}", dec(qd, ck[0], cv[0]),
               decode_ref(qd, ck[0], cv[0], length), text)
        text = jax.jit(lambda a, b, c: dec(a, b, c, layer=1, window=m // 8)
                       ).lower(qd, ck, cv).as_text()
        record(f"flash_decode_layer_window_hd{d}",
               dec(qd, ck, cv, layer=1, window=m // 8),
               decode_ref(qd, ck[1], cv[1], length, window=m // 8), text)

        def quant(x):
            amax = jnp.max(jnp.abs(x.astype(f32)), axis=-1, keepdims=True)
            sc = jnp.maximum(amax / 127.0, 1e-8)
            qv = jnp.clip(jnp.round(x.astype(f32) / sc), -127, 127)
            return qv.astype(jnp.int8), sc[..., 0].astype(jnp.bfloat16)

        k8, k8s = quant(ck[0])
        v8, v8s = quant(cv[0])
        text = jax.jit(lambda a, b, c, e, f: flash_decode(
            a, b, c, jnp.int32(length), e, f, interpret=interp)).lower(
                qd, k8, v8, k8s, v8s).as_text()
        record(f"flash_decode_int8_hd{d}",
               flash_decode(qd, k8, v8, jnp.int32(length), k8s, v8s,
                            interpret=interp),
               decode_ref(qd, k8.astype(f32) * k8s[..., None].astype(f32),
                          v8.astype(f32) * v8s[..., None].astype(f32),
                          length), text)

    # the blockwise cross entropy (scan + matmuls, no Pallas): weighted sum
    # and both gradients against autodiff of the dense path at f32
    from tony_tpu.ops.cross_entropy import (blockwise_cross_entropy,
                                            dense_cross_entropy)

    n, d, v = ce_shape
    ks = jax.random.split(jax.random.PRNGKey(n), 3)
    x = jax.random.normal(ks[0], (n, d), jnp.bfloat16)
    w = (jax.random.normal(ks[1], (d, v), f32) * d ** -0.5).astype(
        jnp.bfloat16)
    t = jax.random.randint(ks[2], (n,), 0, v)
    # a masked mean's weights, every seventh row padding
    valid = (jnp.arange(n) % 7 != 0).astype(f32)
    rw = valid / valid.sum()

    def ce_ref(x, w):
        with jax.default_matmul_precision("highest"):
            return jnp.sum(dense_cross_entropy(x, w, t) * rw)

    ce = jax.jit(jax.value_and_grad(
        lambda x, w: blockwise_cross_entropy(x, w, t, rw), (0, 1)))
    text = ce.lower(x, w).as_text()
    loss, (dx, dw) = ce(x, w)
    want, (want_dx, want_dw) = jax.jit(jax.value_and_grad(ce_ref, (0, 1)))(
        x.astype(f32), w.astype(f32))
    for name, got, ref in (("loss", loss, want), ("dx", dx, want_dx),
                           ("dw", dw, want_dw)):
        record(f"blockwise_ce_{name}_{n}x{d}x{v}", got, ref, text,
               pallas=False)

    for c in cases:
        print(json.dumps(c), flush=True)
    say({"device": device_report(), "cases": cases,
         "cache": {"dir": cache_dir, **counts},
         "seconds": round(time.monotonic() - t0, 1)})
    return 0


# ---------------------------------------------------------------------- main
def run_one_chip(r: Runner, mode: dict, seed: int, only: set | None) -> None:
    want = lambda name: only is None or name in only
    if want("device"):
        phase_device(r, mode)
    if want("kernels"):
        phase_kernels(r, mode)
    ckpt = None
    if want("serve") or want("generate"):
        ckpt = phase_ckpt(r, mode, seed)
        prompts = prompts_from_seed(seed, mode["llama"]["vocab_size"],
                                    mode["requests"])
        served = phase_serve(r, mode, ckpt, prompts)
        if want("generate"):
            phase_generate(r, mode, ckpt, prompts, served)
        shutil.rmtree(ckpt, ignore_errors=True)     # room for the trainer's
    if want("train"):
        phase_train(r, mode)


def run_four_chips(r: Runner, mode: dict, seed: int) -> None:
    ckpt = phase_ckpt(r, mode, seed)
    prompts = prompts_from_seed(seed, mode["llama"]["vocab_size"],
                                mode["requests"])
    # what tensor=4 is compared with: the same server on one of the chips
    one = phase_serve(r, mode, ckpt, prompts, name="serve_one_chip",
                      count=4)
    phase_serve(r, mode, ckpt, prompts, name="serve_tensor4",
                mesh="tensor=4", count=4, compare_to=one)
    held = r.results["serve_tensor4"]["established"]["bytes_in_use"] or []
    if mode["expect"] == "tpu" and not (
            len(held) == 4 and all(b and b > 0 for b in held)):
        say({"phase": "serve_tensor4", "failed": "not every device holds a "
             "share of the model", "bytes_in_use": held})
        raise PhaseFailed(f"serve_tensor4: bytes in use {held}")
    shutil.rmtree(ckpt, ignore_errors=True)
    phase_train4(r, mode)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the paths that span four chips")
    ap.add_argument("--rehearse", action="store_true",
                    help="CPU, toy widths: control flow only; never prints "
                         "the contract's ok line")
    ap.add_argument("--only", default="",
                    help="comma-separated phases of the one-chip run "
                         "(device,kernels,serve,generate,train); a partial "
                         "run never prints the contract's ok line")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=Path, default=OUT,
                    help="logs and results (small; the chip tool copies "
                         "chiprun_out/ back)")
    ap.add_argument("--work", type=Path, default=WORK,
                    help="weights and checkpoints (gigabytes; removed at "
                         "the end)")
    # children of this script
    ap.add_argument("--child", default="")
    ap.add_argument("--config", default="")
    ap.add_argument("--dir", default="")
    ap.add_argument("--spec", default="")
    ap.add_argument("--kernel-len", type=int, default=1024)
    ap.add_argument("--decode-len", type=int, default=4096)
    ap.add_argument("--ce-shape", default="8192,4096,32768")
    args = ap.parse_args(argv)

    if args.child:
        sys.path.insert(0, str(ROOT))
        return {"device": child_device,
                "ckpt": lambda: child_ckpt(args.config, args.dir, args.seed),
                "generate": lambda: child_generate(args.spec),
                "kernels": lambda: child_kernels(
                    args.kernel_len, args.decode_len,
                    tuple(map(int, args.ce_shape.split(",")))),
                }[args.child]()

    if not (ROOT / "tony_tpu" / "__init__.py").is_file():
        print("chip_smoke.py: the tony_tpu package is not beside this "
              "script; there is nothing to smoke", file=sys.stderr)
        return 2
    mode = TOY if args.rehearse else REAL
    count = 4 if args.four_chips else 1
    only = {p.strip() for p in args.only.split(",") if p.strip()} or None
    out, work = args.out.resolve(), args.work.resolve()
    shutil.rmtree(out, ignore_errors=True)
    shutil.rmtree(work, ignore_errors=True)
    out.mkdir(parents=True)
    work.mkdir(parents=True)
    r = Runner(out, work)
    t0 = time.monotonic()
    say({"start": "chip_smoke", "mode": "rehearsal (cpu, toy widths)"
         if args.rehearse else "four chips" if args.four_chips else "one chip",
         "free_disk_gb": round(shutil.disk_usage(ROOT).free / 1e9, 1),
         "compile_cache_env": os.environ.get("JAX_COMPILATION_CACHE_DIR")})
    try:
        if args.rehearse and args.four_chips:
            os.environ["XLA_FLAGS"] = (
                os.environ.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()
        if args.rehearse:
            os.environ["JAX_PLATFORMS"] = "cpu"
        if args.four_chips:
            run_four_chips(r, mode, args.seed)
        else:
            run_one_chip(r, mode, args.seed, only)
    except PhaseFailed as e:
        say({"ok": False, "failed": str(e),
             "seconds": round(time.monotonic() - t0, 1)})
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)
        (out / "results.json").write_text(json.dumps(r.results, indent=1))

    say({"seconds_per_phase": {k: v["seconds"] for k, v in r.results.items()},
         "total_seconds": round(time.monotonic() - t0, 1)})
    triples = {(d["platform"], d["kind"], d["count"])
               for d in r.devices.values()}
    if len(triples) != 1:
        say({"ok": False, "failed": f"phases disagree on the device: "
             f"{sorted(triples)}"})
        return 1
    platform, kind, n = triples.pop()
    device = {"platform": platform, "kind": kind, "count": n}
    if args.rehearse or only is not None:
        say({"rehearsal" if args.rehearse else "partial": "passed",
             "phases": sorted(r.results), "device": device})
        return 0
    if platform != "tpu" or n != count:
        say({"ok": False, "failed": f"ran on {device}"})
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
