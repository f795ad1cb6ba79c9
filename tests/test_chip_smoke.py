"""chip_smoke.py's own guarantees, at toy size on the CPU: a failing child
is a non-zero exit with that child's log in the output, the parent never
imports jax, and without a chip the script fails and says why."""

from __future__ import annotations

import importlib.util
import json
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SMOKE = REPO / "chip_smoke.py"


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SMOKE)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_parent_process_never_imports_jax():
    """A parent that has touched jax holds the chip and every child then
    fails or hangs: after the script's own imports, jax must be absent."""
    code = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('cs', {str(SMOKE)!r})\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(mod)\n"
        "bad = sorted(m for m in sys.modules if m.split('.')[0] in "
        "('jax', 'jaxlib', 'libtpu', 'tony_tpu', 'torch', 'transformers'))\n"
        "print(bad)\n"
        "sys.exit(1 if bad else 0)\n")
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=60)
    assert proc.returncode == 0, proc.stdout + proc.stderr


def _failing_child(log_line: str) -> list[str]:
    return [sys.executable, "-c",
            "import sys; print('child stdout line');"
            f" print({log_line!r}, file=sys.stderr); sys.exit(7)"]


@pytest.mark.parametrize("how", ("exit_code", "check", "timeout"))
def test_phase_runner_reports_a_failed_child(tmp_path, capsys, how):
    """Each way a phase can fail — the child's exit code, the phase's own
    check, its time limit — raises (never records-and-carries-on) and
    echoes the end of the child's logs."""
    smoke = _load_smoke()
    r = smoke.Runner(tmp_path, tmp_path / "work")
    if how == "exit_code":
        cmd, check, timeout = _failing_child("BOOM-7 from the child"), \
            (lambda out: {}), 30
    elif how == "check":
        cmd = [sys.executable, "-c",
               "import sys; print('BOOM-7 from the child', file=sys.stderr)"]

        def check(out):
            raise ValueError("tokens differ")
        timeout = 30
    else:
        cmd = [sys.executable, "-c",
               "import sys, time; print('BOOM-7 from the child', "
               "file=sys.stderr, flush=True); time.sleep(60)"]
        check, timeout = (lambda out: {}), 2
    with pytest.raises(smoke.PhaseFailed):
        r.run("unit", cmd, timeout=timeout, check=check)
    out = capsys.readouterr().out
    lines = [json.loads(l) for l in out.splitlines() if l.startswith("{")]
    assert lines[0]["phase"] == "unit" and "cmd" in lines[0]
    assert "failed" in lines[1] and "established" not in lines[1]
    assert "BOOM-7 from the child" in out       # the child's stderr, echoed
    assert "unit" not in r.results              # a failed phase is no result


def test_smoke_with_a_failing_child_exits_nonzero(tmp_path):
    """End to end through main(): the first phase's child fails (there is
    no TPU here, and the rehearsal flag is not given), the script exits
    non-zero, names the phase, prints the child's log and no ok line."""
    proc = subprocess.run(
        [sys.executable, str(SMOKE), "--only", "device",
         "--out", str(tmp_path / "out"), "--work", str(tmp_path / "work")],
        capture_output=True, text=True, timeout=240, cwd=tmp_path,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and "device" in last["failed"]
    assert "not on platform 'tpu'" in proc.stdout     # says why
    assert '"ok": true' not in proc.stdout


def test_smoke_alone_in_a_directory_fails(tmp_path):
    """In a directory that holds chip_smoke.py and nothing else of the
    repo there is nothing to smoke: non-zero, no result line."""
    alone = tmp_path / "chip_smoke.py"
    alone.write_text(SMOKE.read_text())
    proc = subprocess.run([sys.executable, str(alone)], capture_output=True,
                          text=True, timeout=60, cwd=tmp_path)
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
    assert "tony_tpu" in proc.stderr


def test_token_comparison_resumes_after_a_near_tie_but_not_after_a_fault():
    """Random weights make greedy decoding ill-conditioned, so a difference
    is admitted only where the server's own top-k shows a near-tie with the
    other path's token; the other path then resumes from the server's
    history. Anything else — a token the server never considered, a clear
    gap — ends the comparison as unexplained."""
    smoke = _load_smoke()

    def step(seq):
        return (sum(seq[-3:]) * 7 + len(seq)) % 101

    def model(prompt, n, wrong_at=()):
        seq, out = list(prompt), []
        for _ in range(n):
            nxt = step(seq)
            if len(seq) in wrong_at:
                nxt = (nxt + 1) % 101
            seq.append(nxt)
            out.append(nxt)
        return out

    def served(prompts, tokens, runner_up_gap):
        """The server's answer: its token first, the model's true token
        (where they differ) as runner-up at ``runner_up_gap`` nats."""
        top = []
        for p, toks in zip(prompts, tokens):
            seq, rows = list(p), []
            for t in toks:
                rows.append({t: -1.0, step(seq): -1.0 - runner_up_gap}
                            if step(seq) != t else {t: -1.0, 100: -3.0})
                seq.append(t)
            top.append(rows)
        return {"tokens": tokens, "top": top}

    prompts = [[3, 5, 8], [13, 21]]
    same = [model(p, 12) for p in prompts]
    rep = smoke.compare_with_resync(model, prompts, served(prompts, same, 0))
    assert rep == {"tokens_compared": 24, "ties": [], "unexplained": []}

    # the server flipped ONE near-tie (sequence position 7 of prompt 0) and
    # went on from its own history: one tie, everything else agrees
    tie = [model(prompts[0], 12, wrong_at=(7,)), same[1]]
    rep = smoke.compare_with_resync(model, prompts,
                                    served(prompts, tie, 0.01))
    assert [(d["index"], d["logprob_gap"]) for d in rep["ties"]] == [
        (4, pytest.approx(0.01))]
    assert rep["unexplained"] == [] and rep["tokens_compared"] == 24

    # the same difference with a clear gap is not a tie
    rep = smoke.compare_with_resync(model, prompts,
                                    served(prompts, tie, 1.5))
    assert rep["ties"] == [] and len(rep["unexplained"]) == 1

    # a server whose tokens the other path never had in its top-k
    wrong = served(prompts, tie, 0.01)
    wrong["top"][0][4] = {tie[0][4]: -1.0, 99: -1.01}
    rep = smoke.compare_with_resync(model, prompts, wrong)
    assert rep["unexplained"][0]["logprob_gap"] is None

    # wrong everywhere past position 6: out of near-ties at once
    broken = [model(prompts[0], 24, wrong_at=range(6, 40)), same[1]]
    rep = smoke.compare_with_resync(model, prompts,
                                    served(prompts, broken, 0.01))
    assert len(rep["ties"]) == smoke.MAX_NEAR_TIES
    assert len(rep["unexplained"]) == 1
