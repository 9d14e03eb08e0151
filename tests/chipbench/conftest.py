"""The benchmark's tests import ``chipbench`` from the checkout's root,
and those that read the repo's own ``BENCHMARK.json`` run on two roots:
the repo's own, and a copy of it to which a later PR has added a second
configuration with a cell on each traffic mix (``later_pr``)."""

import os
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


@pytest.fixture(scope="session", params=["repo", "repo+second"])
def root_kind(request) -> str:
    return request.param


@pytest.fixture(scope="session")
def fresh_root(root_kind):
    """``path -> root``: a new copy of this root under ``path``, for a
    test that breaks one."""
    import later_pr

    def build(path) -> str:
        root = later_pr.repo_checkout(path)
        if root_kind == "repo+second":
            later_pr.add_second_configuration(root)
        return root

    return build


@pytest.fixture(scope="session")
def repo_root(root_kind, fresh_root, tmp_path_factory) -> str:
    """The repo's own root itself, then its copy with the addition."""
    if root_kind == "repo":
        return REPO
    return fresh_root(tmp_path_factory.mktemp("second") / "root")


@pytest.fixture(scope="module")
def reg(repo_root):
    from chipbench.registry import Registry

    return Registry(repo_root)
