"""Drive ``bench/run.py`` in this process at its rehearsal sizes and read
back what it printed."""
import contextlib
import io
import json
import re

from bench import run


def rehearse(workload: str, seed: int, *extra) -> str:
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", "1", "--rehearse", *extra])
    assert rc == 0
    return buf.getvalue()


def result(out: str) -> dict:
    for line in out.splitlines():
        if line.startswith("rehearsal: {"):
            return json.loads(line[len("rehearsal: "):])
    raise AssertionError(f"no rehearsal line in:\n{out}")


def control(workload: str, seed: int) -> dict:
    out = rehearse(workload, seed, "--control")
    return {m.group(1): (float(m.group(2)), float(m.group(3))) for m in
            re.finditer(r"^control (\S+): (\S+) \(limit (\S+)\)$", out,
                        re.M)}
