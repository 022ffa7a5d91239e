"""The documents against the tree: every ``MXNET_*`` option the code
reads has a row in docs/env_vars.md and every row an option some file
reads; every repository path a document names in backticks exists."""

import functools
import glob
import os
import re

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# where the program reads its options; tests/conftest.py reads the two
# that steer a test run
_READERS = ('mxnet_tpu', 'tools', 'benchmark', 'chip_smoke.py',
            os.path.join('tests', 'conftest.py'))
# a name is read where it is a string literal (os.environ.get('X'),
# environ['X'], a helper's argument) or a keyword of dict(os.environ, X=..)
_READ = re.compile(r'''['"](MXNET_[A-Z0-9_]+)['"]|\b(MXNET_[A-Z0-9_]+)=''')
_ROW = re.compile(r'^\| `(MXNET_[A-Z0-9_]+)`')
_TOP = ('mxnet_tpu/', 'tools/', 'tests/', 'chipbench/', 'examples/',
        'docs/', 'benchmark/')


def _python_files():
    for entry in _READERS:
        path = os.path.join(REPO, entry)
        if os.path.isfile(path):
            yield path
        for root, _, files in os.walk(path):
            for f in files:
                if f.endswith('.py'):
                    yield os.path.join(root, f)


@functools.lru_cache(maxsize=None)
def _options_read():
    names = set()
    for path in _python_files():
        with open(path, encoding='utf-8') as f:
            for m in _READ.finditer(f.read()):
                names.add(m.group(1) or m.group(2))
    return names


def _option_rows():
    """First cells of the table under '## Framework variables'."""
    rows, inside = [], False
    with open(os.path.join(REPO, 'docs', 'env_vars.md'),
              encoding='utf-8') as f:
        for line in f:
            if line.startswith('## '):
                inside = line.strip() == '## Framework variables'
            m = _ROW.match(line) if inside else None
            if m:
                rows.append(m.group(1))
    return rows


def test_every_option_read_has_a_row():
    rows = _option_rows()
    assert len(rows) > 40
    assert sorted(_options_read() - set(rows)) == []


def test_every_row_names_an_option_some_file_reads():
    rows = _option_rows()
    assert len(rows) == len(set(rows)), 'an option has two rows'
    assert sorted(set(rows) - _options_read()) == []


def _documents():
    docs = sorted(glob.glob(os.path.join(REPO, 'docs', '*.md')))
    return [os.path.join(REPO, 'README.md')] + docs


def _named_paths(text):
    """Backticked spans that begin with a top-level directory of this
    repository, cut at the first blank (`tools/launch.py -n 2`)."""
    for span in re.findall(r'`([^`\n]+)`', text):
        token = span.split()[0].rstrip('.,;')
        if token.startswith(_TOP):
            yield token


def _exists(token):
    """A path, a glob, a path with a line suffix (`file.py:12`,
    `file.py:12-40`) or a test's id (`tests/test_x.py::test_y`, whose
    function the file must define)."""
    path, _, test = token.partition('::')
    path = re.sub(r':\d+(-\d+)?$', '', path)
    found = glob.glob(os.path.join(REPO, path))
    if not found or not test:
        return bool(found)
    with open(found[0], encoding='utf-8') as f:
        return re.search(rf'^\s*def {re.escape(test)}\b', f.read(),
                         re.M) is not None


@pytest.mark.parametrize(
    'doc', _documents(), ids=lambda p: os.path.relpath(p, REPO))
def test_every_path_a_document_names_exists(doc):
    with open(doc, encoding='utf-8') as f:
        text = f.read()
    assert sorted(t for t in set(_named_paths(text))
                  if not _exists(t)) == []
