"""tools/repo_lint.py — the repo-wide AST lint runs clean over the
whole tree (tier-1: a regression in any of its three bug classes fails
the build) and actually catches planted violations of each class."""

import json
import os
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
    import re
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
