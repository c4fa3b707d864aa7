"""The docs consistency checks of tools/check_docs.py, one case each: a
link to a deleted file, or a table that drifted from the live registry,
fails here as it fails the CI `docs` job."""
import importlib.util
import pathlib

import pytest

_PATH = pathlib.Path(__file__).resolve().parents[1] / "tools" / "check_docs.py"
_SPEC = importlib.util.spec_from_file_location("check_docs", _PATH)
check_docs = importlib.util.module_from_spec(_SPEC)
_SPEC.loader.exec_module(check_docs)


@pytest.mark.parametrize("check", check_docs.CHECKS,
                         ids=lambda check: check.__name__)
def test_docs_agree_with_the_code(check):
    assert check() == []
