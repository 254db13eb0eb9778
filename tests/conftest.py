import pytest


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: long-running test")
    # informational when pytest-timeout is absent (offline container); the
    # chaos tests ALSO assert wall-clock bounds themselves, and the CI
    # chaos lane wraps the whole invocation in a shell-level timeout
    config.addinivalue_line(
        "markers", "timeout(seconds): per-test time budget "
        "(enforced by pytest-timeout when installed)")


def pytest_addoption(parser):
    parser.addoption("--skip-slow", action="store_true", default=False)


def pytest_collection_modifyitems(config, items):
    if config.getoption("--skip-slow"):
        skip = pytest.mark.skip(reason="--skip-slow")
        for item in items:
            if "slow" in item.keywords:
                item.add_marker(skip)
