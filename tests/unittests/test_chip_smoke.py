"""chip_smoke.py, rehearsed on the CPU (the chip run itself is sent
through the chip tool; CHANGES.md records it).

What tier-1 can hold on to: the --tiny rehearsal walks every phase and
exits 0 with the contract's last line; a failing phase makes the exit
code non-zero; without --tiny a host with no TPU exits non-zero before
running a step; and importing the entry points initialises no backend,
because tools/ptpu_elastic.py starts
children that need the chip a parent would otherwise be holding.
"""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)     # one CPU device: phase D must skip
    env.update(extra)
    return env


# what a phase's own lines have to say, beside its verdict
PHASE_SAYS = {
    # phase C walked the kernels (interpreted here, Mosaic on the chip) and
    # timed what XLA runs around the delta rule's kernels, part by part
    "C": lambda lines: sum("interpreted;" in line for line in lines) >= 7
    and any("gated_delta_rule's XLA parts" in line
            and "(I + L)^-1 of [2, 4, 1, 64, 64]" in line
            and "_prepare forward + transpose" in line for line in lines),
    # phase F ran the convolution on both of its paths
    "F": lambda lines: all(
        any("causal_conv1d %s path" % path in line
            and "off the float32 recomputation by y " in line
            for line in lines) for path in ("kernel", "xla")),
    # phase J ran the flash kernels' latent form and the hyper-connections'
    # kernels against their float32 references
    "J": lambda lines: any(
        "J flash at 192 | 128 with one rotary key" in line
        and "dk_rope" in line for line in lines) and any(
        "J hyper-connection of 4 streams" in line and "dphi" in line
        and "rows a block" in line for line in lines),
    # phase K held the loss kernel to the float32 formula and timed one
    # head in the parent's form and in PR 44's
    "K": lambda lines: any(
        "K softmax_xent kernel [" in line and "off the formula" in line
        for line in lines) and any(
        "K head [" in line and "now " in line and "parent " in line
        for line in lines),
    # phase L ran the core at 192 + 64 on 256 in both forms, timed each and
    # the plain kernels on joined heads
    "L": lambda lines: any(
        "L latent core at 192 + 64 on 256" in line
        and "whole at blocks" in line and "two_part at blocks" in line
        and "dk_rope" in line and "arriving joined" in line
        for line in lines),
    # phase N held the scan's kernels to lax.scan and one differential core,
    # windowed and full, to the dense two maps
    "N": lambda lines: any(
        "N selective scan kernels" in line and "ddelta" in line
        and "forward + backward" in line for line in lines) and any(
        "N differential core" in line and "window 16 off by" in line
        and "window None off by" in line for line in lines),
    # phase O held the state-space-dual scan's kernels to the recurrence
    # token by token under both decays, and timed the tiles of the sweep
    "O": lambda lines: sum(
        "O ssd kernels, x" in line and "ddelta" in line and "da " in line
        for line in lines) == 2 and any(
        "Delta A = -6 a token" in line for line in lines) and sum(
        "O ssd kernels at chunks of" in line and "forward + backward" in line
        for line in lines) >= 2,
    # phase Q held the KDA kernels to the scan path and to the recurrence
    # under both decays, and timed both paths
    "Q": lambda lines: sum(
        "Q kda kernels, q/k/v/g [2, 72, 2, 16]" in line
        and "off the scan path by out " in line and "dbeta" in line
        and "off the recurrence by at most" in line
        and "forward + backward kernel" in line for line in lines) == 2
    and any("every channel at the bound" in line for line in lines),
    # phase R held the norm a head's two kernels to the jax.numpy lines and
    # their vjp (y, dx, dscale), timed both paths, and swept the tile
    "R": lambda lines: sum(
        "R rms_norm " in line and "dx " in line and "dscale off by" in line
        and "forward + transpose kernel" in line for line in lines) == 3
    and any("a head of 256 [1, 24, 2, 256] bf16, 1 + weight" in line
            for line in lines)
    and any("at blocks of 64 KiB" in line for line in lines),
}


# One case a phase, and the phases are three files' (this one,
# test_chip_smoke_b.py and _c.py, each with PHASES[i::FILES]): under `--dist
# loadfile` a file is one worker's from start to end, and the files of the
# fewest cases are handed out last, so one file of seventeen child processes
# was the run's tail (ROADMAP C8, PR 73). This file, which has five other
# tests and so goes out before the other two, takes the share with phase C
# in it: 97 s of the seventeen phases' 375 on the builder's machine.
PHASES = "ABCDEFGHIJKLNOPQR"
FILES = 3


def tiny_rehearsal_passes(letter):
    """One case a phase (`--phases <letter>`), so that a red run names it."""
    # phase E times the program phase A left
    phases = {"E": "AE"}.get(letter, letter)
    out = subprocess.run([sys.executable, SMOKE, "--tiny", "--phases", phases],
                         env=_env(), cwd=REPO, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, out.stdout + out.stderr
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True,
        "device": {"platform": "cpu", "kind": "cpu", "count": 1}}
    assert lines[0].startswith("chip_smoke: jax=")
    # a CPU line can never be mistaken for a chip line
    assert all("platform=cpu" in line for line in lines[:-1])
    # one CPU device: phase D must skip
    verdict = " skipped: needs 4 devices" if letter == "D" else " passed in "
    ran = [line for line in lines if "] phase " in line]
    assert len(ran) == len(phases) and "phase %s " % letter in ran[-1] \
        and verdict in ran[-1], ran
    if letter in PHASE_SAYS:
        assert PHASE_SAYS[letter](lines)


@pytest.mark.parametrize("letter", PHASES[2::FILES])
def test_tiny_rehearsal_passes_every_phase(letter):
    tiny_rehearsal_passes(letter)


_FAILING_RUN = """
import sys
sys.path.insert(0, %r)
import chip_smoke

def boom(smoke):
    raise RuntimeError("injected failure")

chip_smoke.PHASES = (
    ("A", "fails", boom),
    ("B", "passes", lambda smoke: None),
    ("C", "skips", lambda smoke: "not applicable here"))
sys.exit(chip_smoke.main(sys.argv[1:]))
""" % REPO


def test_a_failing_phase_makes_the_exit_code_nonzero():
    def run(*argv):
        return subprocess.run(
            [sys.executable, "-c", _FAILING_RUN, "--tiny"] + list(argv),
            env=_env(), cwd=REPO, capture_output=True, text=True,
            timeout=300)
    out = run()
    assert out.returncode == 1, out.stdout + out.stderr
    lines = out.stdout.strip().splitlines()
    assert json.loads(lines[-1])["ok"] is False
    assert any("phase A (fails) FAILED" in line for line in lines)
    assert "injected failure" in out.stderr
    # the later phases still ran: one chip call reports every failure
    assert any("phase B (passes) passed" in line for line in lines)
    assert any("phase C (skips) skipped: not applicable here" in line
               for line in lines)
    # and with only passing phases selected the same run exits 0
    assert run("--phases", "BC").returncode == 0


def test_no_phases_given_is_every_phase_there_is():
    """The default of --phases is every letter of PHASES: it read "default
    all" and stopped at M while N existed."""
    script = _FAILING_RUN.replace(
        '("A", "fails", boom)', '("Z", "a later letter", lambda smoke: None)')
    out = subprocess.run([sys.executable, "-c", script, "--tiny"],
                         env=_env(), cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "phase Z (a later letter) passed" in out.stdout
    with open(SMOKE) as f:
        source = f.read()
    assert '("O", "the state-space-dual scan", phase_o)' in source
    assert 'default="ABCDEFGHIJKLM"' not in source


def test_without_a_tpu_nothing_runs():
    out = subprocess.run([sys.executable, SMOKE], env=_env(), cwd=REPO,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 2, out.stdout + out.stderr
    assert "platform=cpu" in out.stdout
    assert "phase" not in out.stdout          # not one step was taken
    assert not out.stdout.strip().splitlines()[-1].startswith("{")
    assert "no TPU" in out.stderr


def test_tiny_needs_the_explicit_cpu_pin():
    env = _env()
    env["JAX_PLATFORMS"] = "tpu,cpu"
    out = subprocess.run([sys.executable, SMOKE, "--tiny"], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 2, out.stdout + out.stderr
    assert out.stdout == ""


def test_importing_the_entry_points_initialises_no_backend():
    code = ("import sys; sys.path.insert(0, %r)\n"
            "import paddle_tpu, chip_smoke\n"
            "from jax._src import xla_bridge\n"
            "assert not xla_bridge._backends, xla_bridge._backends\n"
            % REPO)
    out = subprocess.run([sys.executable, "-c", code], env=_env(),
                         cwd=REPO, capture_output=True, text=True,
                         timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr
