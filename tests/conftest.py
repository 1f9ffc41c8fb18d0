def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: multi-device subprocess drills (seconds to minutes)")
