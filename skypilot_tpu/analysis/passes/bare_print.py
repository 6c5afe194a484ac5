"""`bare-print`: no bare print() outside the stdout-is-the-product set.

Migrated from the ad-hoc walker in tests/unit/test_no_bare_print.py
(ISSUE 4 satellite; the test is now a thin wrapper over this pass).
Diagnostics must go through sky_logging so they land in the log
infrastructure and the flight recorder, not a lost stdout.  AST-based,
not grep-based: codegen modules build ``print(...)`` INSIDE string
literals shipped to remote hosts and those are fine — only real
`print` call nodes count.
"""
from __future__ import annotations

import ast
from typing import Iterator

from skypilot_tpu.analysis import core
from skypilot_tpu.analysis import index as index_lib

# rel-path -> why stdout is the interface there.
ALLOWED = {
    'cli.py': 'click CLI: echo/table output is the product',
    'skylet/log_lib.py': 'log tailing: stdout is the data channel',
    'skylet/attempt_skylet.py': 'spawn status for the invoking shell',
    'native/__init__.py': 'fan-in line mirroring to the supervisor log',
    'models/import_weights.py': 'conversion script: JSON result on stdout',
    'jobs/core.py': 'tail_logs dumps the controller log to stdout',
    'serve/core.py': 'tail_logs dumps the service log to stdout',
    'chaos/elastic_task.py':
        'gang-exec\'d task: stdout is the rank log `sky logs` tails',
    'batch/runner.py':
        'managed-job driver: the summary JSON on stdout is the run '
        'output `sky jobs logs` tails',
}


class BarePrintPass(core.Pass):

    name = 'bare-print'
    rules = ('bare-print', 'bare-print-stale-allow')
    description = ('print() outside the allowlist (use sky_logging); '
                   'stale allowlist entries')

    def run(self, idx: index_lib.PackageIndex) \
            -> Iterator[core.Finding]:
        for rel in sorted(ALLOWED):
            if rel not in idx.modules:
                yield core.Finding(
                    'bare-print-stale-allow', rel, 0,
                    f'allowlisted file {rel!r} no longer exists — '
                    f'shrink the allowlist in analysis/passes/'
                    f'bare_print.py')
        for rel, mod in sorted(idx.modules.items()):
            if rel in ALLOWED:
                continue
            for node in ast.walk(mod.tree):
                if (isinstance(node, ast.Call) and
                        isinstance(node.func, ast.Name) and
                        node.func.id == 'print'):
                    yield core.Finding(
                        'bare-print', rel, node.lineno,
                        'bare print() — use sky_logging.init_logger'
                        '(__name__), or allowlist the file with a '
                        'reason if stdout is its interface')
