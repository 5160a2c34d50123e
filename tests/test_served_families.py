"""The table of served families (tests/served_families.py) against what is
registered and what is bound: a family registered without a row, a row no
test file binds to the engine's contract (tests/family_contract.py), or a
helper of the table's copied into a test file again, fails here."""
import glob
import os
import re

from paddle_tpu.models import family

from served_families import FAMILIES, REPO


def bound_rows():
    """{family name: [test files that bind ``Contract`` to its row]}, read
    from the files' text."""
    bound = {}
    for path in sorted(glob.glob(os.path.join(REPO, 'tests', 'test_*.py'))):
        with open(path) as f:
            text = f.read()
        for name in re.findall(
                r"^class Test\w+\((?:family_contract\.)?Contract\):\n"
                r"    row = FAMILIES\['(\w+)'\]", text, re.M):
            bound.setdefault(name, []).append(os.path.basename(path))
    return bound


def test_every_registered_family_has_a_row():
    registered = {fam.name: cls for cls, fam in family._FAMILIES.items()}
    assert sorted(registered) == sorted(FAMILIES)
    for name, row in FAMILIES.items():
        assert row.name == name == row.family.name
        assert isinstance(row.config(row.shape()), registered[name])
        assert row.config_cls is registered[name]


def test_every_row_is_bound_by_one_test_file():
    bound = bound_rows()
    assert sorted(bound) == sorted(FAMILIES)
    assert all(len(files) == 1 for files in bound.values()), bound


def test_the_tables_helpers_are_defined_in_no_test_file_again():
    """What each ``model_config`` PR once copied into its own file."""
    copied = ('_reference', 'tiny_shape', 'program_config', 'weights',
              'prompts_of', '_serve', '_held_to_reference')
    found = {}
    for path in glob.glob(os.path.join(REPO, 'tests', '*.py')):
        with open(path) as f:
            for name in re.findall(r'^def (\w+)\(', f.read(), re.M):
                if name in copied:
                    found.setdefault(name, []).append(os.path.basename(path))
    assert all(len(files) <= 1 for files in found.values()), found
