"""The table of `BIGDL_TPU_*` names in `utils/config.py` is the program's
list of its environment names: every name the program reads has a row,
every row has a reader, and the number of names is pinned, so a change
that adds one has to say so in the same diff (ROADMAP Design 2)."""

import os
import re

import bigdl_tpu.utils.config as config
from bigdl_tpu.utils.supervisor import PHASES

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: where ROADMAP Design 2 counts names
COUNTED = ("bigdl_tpu", "tools", "chip_smoke.py", "benchmark")
#: where the program, its entry points and the suite's own switch read one
READERS = COUNTED + ("__graft_entry__.py", "tests/conftest.py")

_GETTER = re.compile(r'\bget_(?:int|float|bool|str)\(\s*"([A-Z0-9_]+)"')
_ENVIRON = re.compile(r'(?:environ\.get\(|getenv\(|environ\[)\s*'
                      r'"BIGDL_TPU_([A-Z0-9_]+)"\s*[\],)](?!\s*=[^=])')


def _texts(roots, suffixes):
    for root in roots:
        root = os.path.join(REPO, root)
        if os.path.isfile(root):
            yield root, open(root).read()
            continue
        for d, _, names in os.walk(root):
            for name in names:
                if name.endswith(suffixes):
                    path = os.path.join(d, name)
                    yield path, open(path).read()


def names_read():
    """Names behind a `config.get_*("NAME")` or a read of `os.environ`.
    One reader builds its names: the supervisor's `"SUPERVISE_" + phase`."""
    out = set()
    for path, text in _texts(READERS, (".py",)):
        if path.endswith(os.path.join("utils", "config.py")):
            text = text.replace(config.__doc__, "")
        for m in (*_GETTER.finditer(text), *_ENVIRON.finditer(text)):
            name = m.group(1)
            if name.endswith("_"):
                out |= {name + phase.upper() for phase in PHASES}
            else:
                out.add(name)
    return out


def names_in_table():
    out = set()
    for line in config.__doc__.splitlines():
        if line.startswith("| BIGDL_TPU_"):
            for token in line.split("|")[1].split("/"):
                out.add(token.strip().removeprefix("BIGDL_TPU").lstrip("_"))
    return out


def test_every_name_read_is_in_the_table():
    assert sorted(names_read() - names_in_table()) == []


def test_every_name_in_the_table_is_read():
    assert sorted(names_in_table() - names_read()) == []


def test_name_count_is_pinned():
    """Design 2's count: distinct `BIGDL_TPU_[A-Z0-9_]+` strings in the
    program, its tools and the benchmark (prefixes that documents write,
    like `BIGDL_TPU_FLEET`, count as the grep counts them).  127 before
    PR 29, 117 before PR 47 took the four `BN_*` names out.  Adding a name
    means changing these numbers, and saying why."""
    found = set()
    for _, text in _texts(COUNTED, (".py", ".sh", ".h", ".md", ".json")):
        found |= set(re.findall(r"BIGDL_TPU_[A-Z0-9_]+", text))
    assert len(found) == 113, sorted(found)
    assert len(names_in_table()) == 109
