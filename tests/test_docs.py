"""Documentation health checks, enforced in tier-1 (and by the CI
`docs` job):

  * a docstring-coverage floor over the public API — the in-repo
    equivalent of `interrogate --fail-under` (which the CI docs job
    also runs), so the floor holds even where interrogate is not
    installed;
  * a markdown link check over README.md, docs/ and benchmarks/README.md
    so the reference set cannot rot silently: relative links must
    resolve, intra-doc anchors must match a real heading, and
    repo-path mentions in backticks must exist.
"""
import ast
import re
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent

# The public-API surface the docstring floor covers. Interrogate's CI
# invocation mirrors this list; keep the two in sync.
PUBLIC_MODULES = [
    "src/repro/core/events.py",
    "src/repro/core/eventlog.py",
    "src/repro/core/policies.py",
    "src/repro/core/strategy.py",
    "src/repro/cloud/pricing.py",
    "src/repro/cloud/simulator.py",
    "src/repro/cloud/preemption.py",
    "src/repro/cloud/traces.py",
    "src/repro/cloud/accounting.py",
    "src/repro/cloud/fleet.py",
    "src/repro/cloud/scenarios.py",
    "src/repro/cloud/report.py",
    "src/repro/fl/fleet.py",
    "src/repro/sweep/__init__.py",
    "src/repro/sweep/spec.py",
    "src/repro/sweep/runner.py",
    "src/repro/sweep/stats.py",
    "src/repro/sweep/report.py",
    "src/repro/fl/engines/base.py",
    "src/repro/fl/engines/__init__.py",
    "src/repro/fl/runner.py",
    "src/repro/fl/cluster.py",
    "src/repro/fl/telemetry.py",
    "src/repro/fl/types.py",
    "src/repro/fl/training.py",
    "src/repro/comms/__init__.py",
    "src/repro/comms/payload.py",
    "src/repro/comms/channel.py",
    "src/repro/comms/billing.py",
    "src/repro/forecast/__init__.py",
    "src/repro/forecast/feed.py",
    "src/repro/forecast/predictors.py",
    "src/repro/forecast/calibration.py",
    "src/repro/forecast/decision.py",
    "src/repro/forecast/strategy.py",
    "src/repro/checkpoint/store.py",
    "src/repro/checkpoint/snapshots.py",
    "src/repro/common/tracing.py",
]
DOC_COVERAGE_FLOOR = 0.9

MARKDOWN_FILES = ["README.md", "benchmarks/README.md",
                  "docs/index.md", "docs/architecture.md",
                  "docs/events.md", "docs/markets.md",
                  "docs/sweep.md", "docs/training.md",
                  "docs/reporting.md", "docs/forecasting.md"]


# ---------------------------------------------------------------------------
# Docstring coverage (interrogate-equivalent).
# ---------------------------------------------------------------------------
def _doc_targets(tree: ast.Module):
    """Yield (qualname, has_docstring) for the module, every public
    class, and every public function/method (nested functions and
    `_private` names excluded, mirroring interrogate's
    --ignore-private --ignore-nested-functions)."""
    yield "<module>", ast.get_docstring(tree) is not None
    for node in tree.body:
        if isinstance(node, ast.ClassDef) and not node.name.startswith("_"):
            yield node.name, ast.get_docstring(node) is not None
            for sub in node.body:
                if isinstance(sub, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)) and \
                        not sub.name.startswith("_"):
                    yield (f"{node.name}.{sub.name}",
                           ast.get_docstring(sub) is not None)
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and not node.name.startswith("_"):
            yield node.name, ast.get_docstring(node) is not None


class TestDocstringCoverage:
    @pytest.mark.parametrize("module", PUBLIC_MODULES)
    def test_module_meets_floor(self, module):
        tree = ast.parse((REPO / module).read_text())
        targets = list(_doc_targets(tree))
        missing = [name for name, ok in targets if not ok]
        coverage = 1.0 - len(missing) / len(targets)
        assert coverage >= DOC_COVERAGE_FLOOR, (
            f"{module}: docstring coverage {coverage:.0%} < "
            f"{DOC_COVERAGE_FLOOR:.0%}; missing: {missing}")

    @pytest.mark.parametrize("module", PUBLIC_MODULES)
    def test_module_docstring_present(self, module):
        tree = ast.parse((REPO / module).read_text())
        assert ast.get_docstring(tree), f"{module} has no module docstring"


# ---------------------------------------------------------------------------
# Markdown link check.
# ---------------------------------------------------------------------------
_LINK = re.compile(r"\[[^\]]+\]\(([^)\s]+)\)")
# backticked repo paths like `src/repro/core/events.py`
_CODE_PATH = re.compile(
    r"`((?:src|docs|tests|benchmarks|examples)/[A-Za-z0-9_/.\-]+)`")


def _slugify(heading: str) -> str:
    """GitHub-style anchor slug for a markdown heading."""
    s = heading.strip().lower()
    s = re.sub(r"[`*_]", "", s)
    s = re.sub(r"[^\w\s-]", "", s)
    return re.sub(r"[\s]+", "-", s).strip("-")


def _anchors(md_path: Path):
    return {_slugify(ln.lstrip("#"))
            for ln in md_path.read_text().splitlines()
            if ln.startswith("#")}


class TestMarkdownLinks:
    @pytest.mark.parametrize("md", MARKDOWN_FILES)
    def test_relative_links_resolve(self, md):
        md_path = REPO / md
        broken = []
        for target in _LINK.findall(md_path.read_text()):
            if target.startswith(("http://", "https://", "mailto:")):
                continue                    # external: not checked offline
            path_part, _, anchor = target.partition("#")
            dest = (md_path.parent / path_part).resolve() if path_part \
                else md_path
            if path_part and not dest.exists():
                broken.append(target)
                continue
            if anchor and dest.suffix == ".md" and \
                    anchor not in _anchors(dest):
                broken.append(f"{target} (missing anchor)")
        assert not broken, f"{md}: broken link(s): {broken}"

    @pytest.mark.parametrize("md", MARKDOWN_FILES)
    def test_backticked_repo_paths_exist(self, md):
        text = (REPO / md).read_text()
        missing = [p for p in _CODE_PATH.findall(text)
                   if not (REPO / p).exists()]
        assert not missing, f"{md}: stale repo path(s): {missing}"

    def test_docs_index_links_every_reference_page(self):
        index = (REPO / "docs/index.md").read_text()
        for page in ("architecture.md", "events.md", "markets.md"):
            assert page in index

    def test_readme_points_at_docs(self):
        readme = (REPO / "README.md").read_text()
        for page in ("docs/architecture.md", "docs/events.md",
                     "docs/markets.md", "benchmarks/README.md"):
            assert page in readme, f"README lost its pointer to {page}"
