"""Every PT_* environment name the package's source gives is documented
in README.md, and README.md documents none the source does not give.
Both lists are read from the files: an option added without its README
line fails here, and so does a README line that outlives its option."""
import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
NAME = re.compile(r"\bPT_[A-Z0-9_]+")


def _source_names():
    names = set()
    for base, _, files in os.walk(os.path.join(ROOT, "paddle_tpu")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(base, f)) as fh:
                    names.update(NAME.findall(fh.read()))
    return names


def _readme_names():
    with open(os.path.join(ROOT, "README.md")) as f:
        return set(NAME.findall(f.read()))


def _split(names):
    """Whole names, and the prefixes (`PT_SDC_`: the code tests
    `name.startswith(...)`) that stand for a family of them."""
    return ({n for n in names if not n.endswith("_")},
            {n for n in names if n.endswith("_")})


def test_every_option_in_the_source_is_in_the_readme():
    source, prefixes = _split(_source_names())
    readme, _ = _split(_readme_names())
    assert len(source) > 50     # the walk found the package
    assert sorted(source - readme) == []
    for p in prefixes:          # a family the README never mentions
        assert any(n.startswith(p) for n in readme), p


def test_every_option_in_the_readme_is_in_the_source():
    source, prefixes = _split(_source_names())
    readme, _ = _split(_readme_names())
    stale = {n for n in readme - source
             if not any(n.startswith(p) for p in prefixes)}
    assert sorted(stale) == []
