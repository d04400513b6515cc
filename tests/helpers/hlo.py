"""Reading an HLO module's text: which ops touch a given shape, and whether
they run inside a ``while`` loop.

Shared by the chunk-program checks on the CPU lowering
(``tests/core/test_step_engine.py``) and on the program compiled for a
described v5e (``tests/kernels/test_tpu_compile.py``).
"""
import re

_NAME = r"%?([\w.\-]+)"


def _computations(text):
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?" + _NAME + r" .*\{$", line)
        if head and not line.startswith(" "):
            cur = comps.setdefault(head.group(1), [])
        elif line.strip() == "}":
            cur = None
        elif cur is not None:
            cur.append(line)
    return comps


def _dims(spec):
    return tuple(int(v) for v in spec.split(",") if v)


def ops_on_shapes(text, ops, shapes):
    """``{op: [outside, inside]}``: how many instructions of each op in
    ``ops`` have a result or first operand whose dimensions are in
    ``shapes``, outside any ``while`` body and inside one (with every
    computation a body reaches through calls and fusions)."""
    comps = _computations(text)
    called = lambda lines: {
        m.group(1) for ln in lines for m in re.finditer(
            r"(?:body|condition|to_apply|calls)=" + _NAME, ln)}
    inside, todo = set(), [
        m.group(1) for lines in comps.values() for ln in lines
        for m in re.finditer(r"while\(.*body=" + _NAME, ln)]
    while todo:
        c = todo.pop()
        if c not in inside and c in comps:
            inside.add(c)
            todo += called(comps[c])
    count = {op: [0, 0] for op in ops}
    instr = re.compile(r"\s*(?:ROOT )?" + _NAME + r" = \w+\[([\d,]*)\]\S* "
                       r"([\w\-]+)\(" + _NAME + r"?")
    for name, lines in comps.items():
        found = [m.groups() for m in map(instr.match, lines) if m]
        shape = {res: _dims(dims) for res, dims, _, _ in found}
        for _, dims, op, arg in found:
            if op in count and (_dims(dims) in shapes
                                or shape.get(arg) in shapes):
                count[op][name in inside] += 1
    return count
