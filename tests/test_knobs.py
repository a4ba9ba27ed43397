"""The environment names the program can be steered by do not grow.

Every ``TPUMS_*`` / ``FLINK_MS_*`` name is a configuration somebody has to
test and document (ROADMAP D10).  The count below is what the tree holds;
a change that needs a new name first retires an old one."""

import io
import pathlib
import re
import tokenize

ROOT = pathlib.Path(__file__).resolve().parent.parent

# distinct names in the string literals of flink_ms_tpu/, native/ and
# chip_smoke.py; lower it whenever a name goes
MAX_ENV_NAMES = 113

_KNOB = re.compile(r"\b(?:TPUMS|FLINK_MS)_[A-Z0-9_]+")
_BENCH = re.compile(r"\bBENCH_[A-Z0-9_]+")
_C_STRING = re.compile(r'"((?:[^"\\\n]|\\.)*)"')


def _sources(*roots):
    for root in roots:
        path = ROOT / root
        if path.is_file():
            yield path
            continue
        for p in sorted(path.rglob("*")):
            if p.suffix in (".py", ".cpp", ".h", ".sh") \
                    and "__pycache__" not in p.parts:
                yield p


def _string_literals(path: pathlib.Path):
    text = path.read_text()
    if path.suffix == ".py":
        for tok in tokenize.generate_tokens(io.StringIO(text).readline):
            if tok.type == tokenize.STRING:
                yield tok.string
    elif path.suffix == ".sh":
        yield text
    else:
        yield from _C_STRING.findall(text)


def _names(pattern, *roots):
    found = {}
    for path in _sources(*roots):
        for literal in _string_literals(path):
            for name in pattern.findall(literal):
                found.setdefault(name, str(path.relative_to(ROOT)))
    return found


def test_environment_names_do_not_grow():
    names = _names(_KNOB, "flink_ms_tpu", "native", "chip_smoke.py")
    assert len(names) > 50, "the scan found too little to mean anything"
    assert len(names) <= MAX_ENV_NAMES, (
        f"{len(names)} TPUMS_* / FLINK_MS_* names, {MAX_ENV_NAMES} allowed: "
        "turn one into a constant or a measurement first (ROADMAP D10). "
        f"All of them: {sorted(names)}")


def test_no_bench_name_is_read_outside_the_benchmark():
    names = _names(_BENCH, "flink_ms_tpu", "native", "scripts",
                   "chip_smoke.py", "__graft_entry__.py")
    assert not names, (
        "BENCH_* names belong to benchmark/, whose cells are data files; "
        "turn one into a constant or a measurement first (ROADMAP D10): "
        f"{names}")
