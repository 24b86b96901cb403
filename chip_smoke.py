#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the repo's main paths once each, through the entry points a user
calls, at the full published width AND depth of one public model
(``qwen3-0.6b``: 28 layers, hidden 1024, 16/8 heads of 128, vocab 151,936;
random weights from a seed):

    python chip_smoke.py            # one TPU chip: train, then serve
    python chip_smoke.py --chips 4  # four chips: FSDP vs its one-chip run

One chip (what the driver runs): ``01-single-chip/train_llm.py`` takes a few
optimiser steps with the Pallas flash kernel forced, then
``python -m distributed_training_guide_tpu.serve`` answers a few requests
with the Pallas paged attend forced, and once more with the gather reference
(``--attend-impl xla``) so the kernel's tokens have something to agree with;
last, ``tests/onchip/kernel_parity.py`` holds both kernels to their XLA
references on random inputs (greedy tokens on random weights are a coarse
check; see ``MIN_PREFIX_AGREEMENT``).

``--chips 4`` runs ONLY ``04-fully-sharded-data-parallel/train_llm.py`` on
the four chips in one process and the one-chip run it is compared with.

This parent never imports JAX: a process that has touched JAX holds the chip,
and a child that needs it then fails or hangs. Each phase is one child
process, run in sequence with a time limit, through the entry point's own
command line; the environment goes through unchanged, so the children share
one compile cache (``utils/compile_cache.py``). The parent reads what the
children print: every child must report the expected platform and the forced
kernel in its start-up device line, and exit 0. A child that reports another
platform is stopped at once.

Lines before the last are notes for a reader (step times, compile seconds,
compile-cache hits and misses, peak HBM), each named for what it is; none is a
benchmark result. The LAST line of stdout is, on success, exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

and on any failure the script says what failed, prints no such line and
exits non-zero.
"""
from __future__ import annotations

import argparse
import ast
import json
import math
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent

# ---- what is run (module constants: a rehearsal patches these, the script
# ---- grows no option or environment variable for it) ----------------------
EXPECT_PLATFORM = "tpu"
PRESET = "qwen3-0.6b"
VOCAB = 151_936            # the preset's vocabulary (models/llama.py)
SEED = 0

TRAIN_SCRIPT = REPO / "01-single-chip" / "train_llm.py"
FSDP_SCRIPT = REPO / "04-fully-sharded-data-parallel" / "train_llm.py"
SERVE_CMD = [sys.executable, "-m", "distributed_training_guide_tpu.serve"]
PARITY_SCRIPT = REPO / "tests" / "onchip" / "kernel_parity.py"

SEQ = 2048
GLOBAL_BATCH = 8           # 13.5 of 15.75 GiB by the compiler's count
TRAIN_STEPS = 8
LOSS_CHUNKS = 16           # the 151,936-wide logits never materialise whole
LR = 1e-3
FIRST_LOSS_BAND = 1.0      # |first loss - ln(VOCAB)|: an untrained model
TRAIN_LIMIT_S = 540

N_NEW = 32
PAGE = 16
CHUNK = 64
MAX_LEN = 512
PROMPT_LENS = (5, 24, 100, 40)   # one > a page, one > a prefill chunk
SERVE_LIMIT_S = 240
PARITY_LIMIT_S = 240
# Greedy tokens of the Pallas attend against the gather reference
# (--attend-impl xla), same weights, same prompts. Two rules, both must hold:
# every request's first MIN_FIRST_TOKENS new tokens are identical (the
# prefill chunks' token and the first T=1 decode step's), and over all
# requests the shared prefix is at least MIN_PREFIX_AGREEMENT of the
# generated length. Not 1.0: the two programs round differently, random
# weights leave the top two logits close, and one flip then diverges the rest
# of that request (read on the chip: 0.8203, three requests identical, one
# flipping at its 10th token).
# The control (the same serve run with the kernel sabotaged four ways: returns
# zeros / reads v as k / block table rolled by a page / masks one position
# too many) was run on the chip at this size: the rules refused all four
# (three share no token with the reference; the off-by-one mask parts from
# it after 1, 7 and 22 tokens in three requests, share 0.4844). On a tiny
# model on the CPU the same rules let "zeros" and the off-by-one through —
# how much an argmax depends on attention is a property of the weights — so
# this check is not leaned on alone: the last phase,
# tests/onchip/kernel_parity.py, holds the kernels to their references on
# random inputs and runs those four sabotages (and a fifth: the layer's
# page base left out of the stacked pools) as its own control.
MIN_FIRST_TOKENS = 2
MIN_PREFIX_AGREEMENT = 0.6

# --chips 4: FSDP against the one-chip run, same seed, data and global batch
LOSS_RTOL = 0.01           # bf16 compute, reductions in another order
MEMORY_RATIO = 1.5         # max/min bytes_in_use over the four devices
# FSDP reduces gradients by reduce-scatter; an all-reduce this large in the
# step program would be a parameter's gradient going the expensive way (the
# all-reduces that belong there are norms, loss statistics and scalars)
MAX_ALL_REDUCE_BYTES = 1 << 20


class SmokeFailure(Exception):
    """A phase did not do what it must; the message says what."""


def note(**fields) -> None:
    print(json.dumps(fields), flush=True)


# ---------------------------------------------------------------------------
# children
# ---------------------------------------------------------------------------

def _json_line(line: str, key: str):
    """The dict of a JSON line that has ``key``, else None."""
    line = line.strip()
    if not (line.startswith("{") and f'"{key}"' in line):
        return None
    try:
        obj = json.loads(line)
    except ValueError:
        return None
    return obj if isinstance(obj, dict) and key in obj else None


def run_child(name: str, cmd: list, limit_s: float) -> list[str]:
    """Run one phase as a child process; return its output lines (stdout and
    stderr, in order). Raises ``SmokeFailure`` on a non-zero exit, on the
    time limit, and — without waiting for the end — as soon as the child's
    device line names another platform than ``EXPECT_PLATFORM``."""
    t0 = time.monotonic()
    proc = subprocess.Popen(
        [str(c) for c in cmd], stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT, text=True, cwd=REPO,
        start_new_session=True)
    lines: list[str] = []

    def pump():
        for line in proc.stdout:
            lines.append(line.rstrip("\n"))

    reader = threading.Thread(target=pump, daemon=True)
    reader.start()
    seen, why = 0, None

    def other_platform():
        """Why, if a device line read since the last look names another
        platform."""
        nonlocal seen
        found = None
        for line in lines[seen:]:
            dev = _json_line(line, "device")
            if dev and dev["device"].get("platform") != EXPECT_PLATFORM:
                found = (f"runs on platform "
                         f"{dev['device'].get('platform')!r}, not "
                         f"{EXPECT_PLATFORM!r}")
        seen = len(lines)
        return found

    try:
        while proc.poll() is None and why is None:
            time.sleep(0.1)
            if time.monotonic() - t0 > limit_s:
                why = f"exceeded its {limit_s:.0f} s limit"
            why = other_platform() or why
    finally:
        if proc.poll() is None:
            _stop(proc)
    reader.join(timeout=10)
    # a child that ended between two looks (a fast one, a loaded machine)
    # still has its last lines read
    why = why or other_platform()
    if why is None and proc.returncode != 0:
        why = f"exited with code {proc.returncode}"
    if why is not None:
        tail = "\n".join(lines[-40:])
        raise SmokeFailure(f"{name}: child {why}\n--- last output ---\n{tail}")
    note(phase=name, wall_s=round(time.monotonic() - t0, 1))
    return lines


def _stop(proc: subprocess.Popen) -> None:
    """Stop the child and whatever it started (its own session)."""
    for sig in (signal.SIGTERM, signal.SIGKILL):
        try:
            os.killpg(proc.pid, sig)
        except ProcessLookupError:
            return
        try:
            proc.wait(timeout=10)
            return
        except subprocess.TimeoutExpired:
            continue


def device_of(name: str, lines: list[str], impl_key: str, impl: str) -> dict:
    """The child's start-up device line, held to the expected platform and
    the forced implementation."""
    for line in lines:
        obj = _json_line(line, "device")
        if obj is None:
            continue
        dev = obj["device"]
        if dev.get("platform") != EXPECT_PLATFORM:
            raise SmokeFailure(f"{name}: ran on {dev}, not {EXPECT_PLATFORM}")
        got = obj.get(impl_key, {}).get("impl")
        if got != impl:
            raise SmokeFailure(f"{name}: {impl_key} resolved to {got!r} "
                               f"({obj.get(impl_key)}), not {impl!r}")
        return dev
    raise SmokeFailure(f"{name}: printed no device line")


def cache_use(lines: list[str]) -> dict | None:
    """The child's own count of compile-cache hits and misses (JAX's
    monitoring events, ``utils/compile_cache.py``): a second run's hits."""
    obj = next((o for o in (_json_line(l, "compile_cache_use")
                            for l in lines) if o), None)
    return obj and obj["compile_cache_use"]


# ---------------------------------------------------------------------------
# training
# ---------------------------------------------------------------------------

def train_cmd(script: Path, per_replica_batch: int, save_dir: str) -> list:
    # enough synthetic tokens for the steps asked, and no more
    tokens = (TRAIN_STEPS + 2) * GLOBAL_BATCH * SEQ
    return [sys.executable, script, "-m", PRESET, "-d", f"synthetic:{tokens}",
            "-s", SEQ, "-b", per_replica_batch, "--seed", SEED, "--lr", LR,
            "--num-epochs", 1, "--max-steps", TRAIN_STEPS, "--log-freq", 1,
            "--attn-impl", "flash", "--checkpoint-activations",
            "--loss-chunks", LOSS_CHUNKS, "--save-dir", save_dir]


def train_infos(lines: list[str]) -> list[dict]:
    """The per-step info dicts the training loop logs (Python dict repr)."""
    out = []
    for line in lines:
        at = line.find("{'global_step'")
        if at < 0:
            continue
        try:
            info = ast.literal_eval(line[at:])
        except (ValueError, SyntaxError):
            # nan/inf are not literals: a non-finite loss lands here
            raise SmokeFailure(f"unreadable (non-finite?) step line: {line}")
        out.append(info)
    return out


def run_train(name: str, script: Path, per_replica_batch: int) -> dict:
    """One training child: >= 6 steps, finite loss that starts where an
    untrained model's must (ln VOCAB) and is lower at the last logged step."""
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as save_dir:
        lines = run_child(name, train_cmd(script, per_replica_batch, save_dir),
                          TRAIN_LIMIT_S)
    dev = device_of(name, lines, "attention", "flash")
    infos = train_infos(lines)
    losses = [i["running_loss"] for i in infos]
    if len(infos) < 6 or infos[-1]["global_step"] < 6:
        raise SmokeFailure(f"{name}: logged {len(infos)} steps, need >= 6")
    if not all(isinstance(x, float) and math.isfinite(x) for x in losses):
        raise SmokeFailure(f"{name}: non-finite loss in {losses}")
    if abs(losses[0] - math.log(VOCAB)) > FIRST_LOSS_BAND:
        raise SmokeFailure(
            f"{name}: first loss {losses[0]} is not within {FIRST_LOSS_BAND} "
            f"of ln({VOCAB}) = {math.log(VOCAB):.3f}")
    if not losses[-1] < losses[0]:
        raise SmokeFailure(f"{name}: loss did not fall: {losses}")
    program = next((o["step_program"] for o in (
        _json_line(l, "step_program") for l in lines) if o), None)
    if program is None:
        raise SmokeFailure(f"{name}: printed no step_program line")
    steady = sorted(i["time/step"] for i in infos[2:])
    note(phase=name, model=PRESET, seq=SEQ, global_batch=GLOBAL_BATCH,
         steps=infos[-1]["global_step"], loss_per_step=losses,
         step_compile_s=program["compile_s"],
         first_step_ms=infos[0]["time/step"],
         median_later_step_ms=steady[len(steady) // 2],
         tokens_per_s_last_step=infos[-1]["tokens_per_s"],
         peak_hbm_gb_memory_stats=infos[-1].get("peak_alloc_gb"),
         compile_cache_use=cache_use(lines))
    return {"device": dev, "losses": losses, "lines": lines,
            "program": program}


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def prompts() -> list[list[int]]:
    """Token ids from the seed: fixed, in range, no two prompts alike."""
    out = []
    for r, n in enumerate(PROMPT_LENS):
        out.append([(SEED * 7919 + 101 * r + 37 * i * i + 11 * i + 3)
                    % (VOCAB - 1) for i in range(n)])
    return out


def serve_cmd(attend_impl: str) -> list:
    cmd = SERVE_CMD + ["-m", PRESET, "--seed", SEED, "--steps", N_NEW,
                       "--n-slots", len(PROMPT_LENS), "--page-size", PAGE,
                       "--max-len", MAX_LEN, "--prefill-chunk", CHUNK,
                       "--attend-impl", attend_impl, "--temperature", 0.0]
    for p in prompts():
        cmd += ["--prompt-ids", ",".join(map(str, p))]
    return cmd


def run_serve(name: str, attend_impl: str) -> dict:
    """One serve child through the CLI's offline path: every request comes
    back with its prompt and exactly N_NEW new, in-range token ids."""
    lines = run_child(name, serve_cmd(attend_impl), SERVE_LIMIT_S)
    dev = device_of(name, lines, "attend", attend_impl)
    results = sorted((obj for obj in (_json_line(l, "token_ids")
                                      for l in lines) if obj),
                     key=lambda o: o["request_id"])
    want = prompts()
    if len(results) != len(want):
        raise SmokeFailure(f"{name}: {len(results)} results for "
                           f"{len(want)} requests")
    generated = []
    for res, prompt in zip(results, want):
        ids = res["token_ids"]
        new = ids[len(prompt):]
        if ids[:len(prompt)] != prompt or len(new) != N_NEW:
            raise SmokeFailure(
                f"{name}: request {res['request_id']} returned "
                f"{len(new)} new tokens (finish {res.get('finish_reason')}) "
                f"after a {len(prompt)}-token prompt, want {N_NEW}")
        if not all(isinstance(t, int) and 0 <= t < VOCAB for t in new):
            raise SmokeFailure(f"{name}: token id out of range in {new}")
        generated.append(new)
    stats = next((o["stats"] for o in (_json_line(l, "stats")
                                       for l in lines) if o), {})
    note(phase=name, model=PRESET, attend_impl=attend_impl,
         requests=len(results), prompt_lens=list(PROMPT_LENS),
         new_tokens_each=N_NEW, token_ids=generated,
         generate_wall_s_compile_included=stats.get("wall_s"),
         compile_cache_use=cache_use(lines))
    return {"device": dev, "generated": generated}


def shared_prefixes(a: list[list[int]], b: list[list[int]]) -> list[int]:
    out = []
    for x, y in zip(a, b):
        n = 0
        while n < len(x) and n < len(y) and x[n] == y[n]:
            n += 1
        out.append(n)
    return out


def check_agreement(kernel: list[list[int]], reference: list[list[int]],
                    what: str = "pallas paged attend vs gather reference"
                    ) -> None:
    """Hold the kernel's greedy tokens to the reference's by the two rules
    at ``MIN_FIRST_TOKENS`` / ``MIN_PREFIX_AGREEMENT``."""
    shared = shared_prefixes(kernel, reference)
    share = sum(shared) / sum(len(x) for x in reference)
    note(check=what, shared_prefix_per_request=shared,
         need_each=MIN_FIRST_TOKENS, shared_prefix_share=round(share, 4),
         need_share=MIN_PREFIX_AGREEMENT)
    if min(shared) < MIN_FIRST_TOKENS:
        raise SmokeFailure(
            f"serve: request {shared.index(min(shared))} leaves the gather "
            f"reference after {min(shared)} new tokens (each request must "
            f"share its first {MIN_FIRST_TOKENS}); shared prefixes {shared}")
    if share < MIN_PREFIX_AGREEMENT:
        raise SmokeFailure(
            f"serve: the Pallas attend's greedy tokens share only "
            f"{share:.3f} of their length with the gather reference's "
            f"(need >= {MIN_PREFIX_AGREEMENT}); shared prefixes {shared}")


def run_parity() -> dict:
    """The kernels of both paths against their XLA references on random
    inputs, in one child (``tests/onchip/kernel_parity.py``): every case
    inside its bound, every sabotaged kernel of its control refused."""
    name = "kernel_parity"
    lines = run_child(name, [sys.executable, PARITY_SCRIPT], PARITY_LIMIT_S)
    dev = device_of(name, lines, "attend", "flash")
    done = next((o for o in (_json_line(l, "kernel_parity_ok")
                             for l in lines) if o), None)
    if not (done and done["kernel_parity_ok"] is True and done.get("cases")
            and done.get("controls_refused")):
        raise SmokeFailure(f"{name}: no passing last line, got {done}")
    cases = [o for o in (_json_line(l, "kernel") for l in lines) if o]
    note(phase=name, cases=len(cases),
         worst_err_over_bound=max(
             e / (c["rtol"] * max(1.0, m)) for c in cases
             for e, m in c["max_abs_err_and_ref_max"].values()),
         controls_refused=done["controls_refused"],
         compile_cache_use=cache_use(lines))
    return {"device": dev}


# ---------------------------------------------------------------------------
# the two runs
# ---------------------------------------------------------------------------

def one_chip() -> dict:
    train = run_train("train_single", TRAIN_SCRIPT, GLOBAL_BATCH)
    flash = run_serve("serve_flash", "flash")
    gather = run_serve("serve_xla_reference", "xla")
    check_agreement(flash["generated"], gather["generated"])
    parity = run_parity()
    for phase in (flash, gather, parity):
        if phase["device"] != train["device"]:
            raise SmokeFailure(f"children disagree on the device: "
                               f"{phase['device']} vs {train['device']}")
    return train["device"]


def check_memory_spread(lines: list[str], n_devices: int) -> None:
    """The FSDP child's per-device ``bytes_in_use`` after its first step:
    all devices present, all non-zero, within ``MEMORY_RATIO`` of each other
    (code that has only run on virtual devices may leave it all on one)."""
    obj = next((o for o in (_json_line(l, "device_memory")
                            for l in lines) if o), None)
    if obj is None:
        raise SmokeFailure("fsdp: printed no device_memory line")
    used = [d.get("bytes_in_use") for d in obj["device_memory"]]
    note(check="fsdp state spread", bytes_in_use_per_device=used,
         need_max_over_min=MEMORY_RATIO)
    if len(used) != n_devices or not all(used):
        raise SmokeFailure(f"fsdp: bytes_in_use {used}: want {n_devices} "
                           f"devices, all non-zero")
    if max(used) / min(used) > MEMORY_RATIO:
        raise SmokeFailure(f"fsdp: bytes_in_use {used} spread wider than "
                           f"{MEMORY_RATIO}x: the state is not sharded evenly")


def check_collectives(program: dict) -> None:
    """The FSDP child's compiled step program (the executable that took the
    steps, summarised by ``utils/hlo.collective_summary``) must gather its
    parameters and reduce its gradients by reduce-scatter. The chip's
    compiler writes a reduce-scatter three ways — the op, an
    ``all-reduce-scatter`` custom fusion, or a ring of collective-permutes
    around the partial dots (windowed einsum) — so any of the three counts,
    and what must NOT be there is an all-reduce the size of a parameter."""
    summary = program.get("collectives")
    if summary is None:
        raise SmokeFailure("fsdp: the step_program line has no collectives")
    counts = summary["counts"]
    note(check="fsdp step program collectives", compiled_hlo_counts=counts,
         largest_all_reduce_bytes=summary["largest_all_reduce_bytes"],
         need_largest_all_reduce_below=MAX_ALL_REDUCE_BYTES)
    if not counts.get("all-gather"):
        raise SmokeFailure(f"fsdp: the compiled step holds {counts}: no "
                           f"parameter all-gather")
    if not (counts.get("reduce-scatter") or counts.get("reduce-scatter-fusion")
            or counts.get("collective-permute")):
        raise SmokeFailure(f"fsdp: the compiled step holds {counts}: no "
                           f"gradient reduce-scatter in any of its forms")
    if summary["largest_all_reduce_bytes"] >= MAX_ALL_REDUCE_BYTES:
        raise SmokeFailure(
            f"fsdp: the compiled step all-reduces "
            f"{summary['largest_all_reduce_bytes']} bytes in one op "
            f"(need < {MAX_ALL_REDUCE_BYTES}): a gradient is all-reduced, "
            f"not reduce-scattered")


def four_chips(n: int = 4) -> dict:
    fsdp = run_train("train_fsdp", FSDP_SCRIPT, GLOBAL_BATCH // n)
    if fsdp["device"].get("count") != n:
        raise SmokeFailure(f"fsdp: ran on {fsdp['device']}, want {n} devices")
    check_memory_spread(fsdp["lines"], n)
    check_collectives(fsdp["program"])
    single = run_train("train_single", TRAIN_SCRIPT, GLOBAL_BATCH)
    if len(fsdp["losses"]) != len(single["losses"]):
        raise SmokeFailure(f"fsdp logged {len(fsdp['losses'])} steps, the "
                           f"one-chip run {len(single['losses'])}")
    worst = max(abs(a - b) / abs(b)
                for a, b in zip(fsdp["losses"], single["losses"]))
    note(check="fsdp vs one-chip loss trajectory",
         worst_relative_difference=worst, need=LOSS_RTOL)
    if worst > LOSS_RTOL:
        raise SmokeFailure(
            f"fsdp and one-chip losses differ by {worst:.4f} relative "
            f"(need <= {LOSS_RTOL}): {fsdp['losses']} vs {single['losses']}")
    return fsdp["device"]


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: only the FSDP run on four chips and the "
                             "one-chip run it is compared with")
    args = parser.parse_args(argv)
    try:
        dev = one_chip() if args.chips == 1 else four_chips()
        if dev.get("count") != args.chips:
            raise SmokeFailure(f"ran on {dev.get('count')} device(s), "
                               f"--chips asked for {args.chips}")
    except SmokeFailure as exc:
        print(f"chip_smoke FAILED: {exc}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev["platform"], "kind": dev["device_kind"],
        "count": dev["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
