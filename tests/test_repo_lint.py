"""tools/repo_lint.py — the repo-wide AST lint runs clean over the
whole tree (tier-1: a regression in any of its three bug classes fails
the build) and actually catches planted violations of each class. Also
what the documents may say: every repo path they name exists, and one
program takes a workload and states a speed."""

import glob
import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

sys.path.insert(0, REPO)

from tools.repo_lint import lint_source, lint_tree  # noqa: E402


def test_repo_tree_is_clean():
    violations = lint_tree(REPO)
    assert not violations, '\n'.join(v.format() for v in violations)


def test_knob_census():
    """Every whole PADDLE_TPU_* name the package reads (prefixes that
    end in ``_`` are families, not names). A PR that adds one raises
    the number here, in its diff."""
    names = set()
    for root, _, files in os.walk(os.path.join(REPO, 'paddle_tpu')):
        for f in files:
            if f.endswith('.py'):
                with open(os.path.join(root, f)) as src:
                    names.update(re.findall(r'PADDLE_TPU_[A-Z0-9_]+',
                                            src.read()))
    whole = {n for n in names if not n.endswith('_')}
    assert len(whole) <= 61, sorted(whole)
    assert not whole & {'PADDLE_TPU_AOT_CACHE', 'PADDLE_TPU_AOT_CACHE_DIR',
                        'PADDLE_TPU_PAGED_PALLAS'}


def _documents():
    return [os.path.join(REPO, 'README.md')] + \
        sorted(glob.glob(os.path.join(REPO, 'docs', '*.md')))


def test_documents_name_only_paths_that_exist():
    """Every repo path in backticks in README.md and docs/*.md that
    ends in .py, .md or .json and starts at a top-level name exists: a
    path under a top-level directory, or a record's name in capitals
    at the root (PERF.md, BENCHMARK.json). A placeholder
    (``configs/<config>.json``) names no file."""
    top = set(os.listdir(REPO))
    missing = []
    for doc in _documents():
        with open(doc) as f:
            text = f.read()
        for path in re.findall(r'`([^`\s]+\.(?:py|md|json))`', text):
            if re.search(r'[<>*{}$]', path):
                continue
            head, slash, _ = path.partition('/')
            rooted = (head in top and os.path.isdir(
                os.path.join(REPO, head))) if slash else \
                re.fullmatch(r'[A-Z][A-Z0-9_]*(_r\d+)?\.(md|json)', path)
            if rooted and not os.path.exists(os.path.join(REPO, path)):
                missing.append('%s: %s' % (os.path.relpath(doc, REPO),
                                           path))
    assert not missing, '\n'.join(missing)


def test_one_program_takes_a_workload_and_states_a_speed():
    """``benchmark/run.py`` is the one program that takes a workload
    and states a speed. No document, and no file of the package, the
    tools, the examples or the tests (the benchmark's own aside), names
    the programs that used to stand beside it or passes the flag, but a
    document showing ``benchmark/run.py``'s own command line."""
    # spelled in halves: this file is among the files searched
    program = re.compile(r'(?<![A-Za-z0-9_])' + 'bench' + r'\.py|'
                         + '|'.join(p + '_' + q for p, q in (
                             ('decode', 'bench'), ('serving', 'bench'),
                             ('conv_bwd', 'microbench'))))
    flag = '--' + 'workload'
    files = _documents() + [os.path.join(REPO, 'chip_smoke.py')]
    for sub in ('paddle_tpu', 'tools', 'examples', 'tests'):
        for root, dirs, names in os.walk(os.path.join(REPO, sub)):
            dirs[:] = [d for d in dirs if d != '__pycache__' and
                       os.path.join(root, d) !=
                       os.path.join(REPO, 'tests', 'benchmark')]
            files.extend(os.path.join(root, n) for n in names)
    found = []
    for path in files:
        try:
            with open(path, encoding='utf-8') as f:
                lines = f.read().splitlines()
        except UnicodeDecodeError:
            continue                    # a built object, not a source
        document = path.endswith('.md')
        for i, line in enumerate(lines, 1):
            named = program.search(line)
            flagged = flag in line and not (
                document and 'benchmark/run.py' in line)
            if named or flagged:
                found.append('%s:%d: %s' % (os.path.relpath(path, REPO),
                                            i, line.strip()))
    assert not found, '\n'.join(found)


def test_cli_exit_codes_and_json(tmp_path):
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'repo_lint.py'),
         '--json'], capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stdout + r.stderr
    rep = json.loads(r.stdout)
    assert rep['count'] == 0 and rep['violations'] == []

    pkg = tmp_path / 'paddle_tpu' / 'ops'
    pkg.mkdir(parents=True)
    (pkg / 'bad.py').write_text(
        'import os\n'
        "K = os.environ.get('PADDLE_TPU_K')\n")
    r = subprocess.run(
        [sys.executable, os.path.join(REPO, 'tools', 'repo_lint.py'),
         '--root', str(tmp_path), '--json'],
        capture_output=True, text=True, timeout=120)
    assert r.returncode == 1
    rep = json.loads(r.stdout)
    assert rep['count'] == 1
    assert rep['violations'][0]['code'] == 'import-time-env'


@pytest.mark.parametrize('code,source,env_scoped', [
    ('import-time-env', "import os\nX = os.environ.get('A')\n", True),
    ('import-time-env', "import os\nX = os.getenv('A')\n", True),
    ('import-time-env',
     "import os\ndef f(x=os.environ.get('A')):\n    return x\n", True),
    ('import-time-env',
     "from ..core.flags import get_flag\nB = get_flag('use_bf16')\n",
     True),
    ('import-time-env',
     "import os\nclass C:\n    K = os.environ.get('A')\n", True),
    ('bare-except',
     'def f():\n    try:\n        pass\n    except:\n        pass\n',
     False),
    ('mutable-default', 'def f(x=[]):\n    return x\n', False),
    ('mutable-default', 'def f(*, x={}):\n    return x\n', False),
    ('mutable-default', 'def f(x=dict()):\n    return x\n', False),
])
def test_catches_each_class(code, source, env_scoped):
    out = lint_source('x.py', source, env_scoped=env_scoped)
    assert any(v.code == code for v in out), \
        [v.format() for v in out]


@pytest.mark.parametrize('source,env_scoped', [
    # env read inside a function body: per-call, allowed everywhere
    ("import os\ndef f():\n    return os.environ.get('A')\n", True),
    # module-level env read OUTSIDE the scoped dirs is fine
    ("import os\nX = os.environ.get('A')\n", False),
    ('def f(x=None):\n    x = x or []\n    return x\n', True),
    ('def f():\n    try:\n        pass\n    except Exception:\n'
     '        pass\n', True),
    ('def f(x=(1, 2)):\n    return x\n', True),
])
def test_allows_clean_patterns(source, env_scoped):
    assert lint_source('x.py', source, env_scoped=env_scoped) == []
