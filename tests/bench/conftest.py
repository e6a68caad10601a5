import os
import sys

import pytest

# the benchmark package lives at the checkout root, beside src/
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
for path in (os.path.join(ROOT, "src"), ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)


@pytest.fixture(scope="session", autouse=True)
def warm_every_bucket():
    """The small cells' active sets fall to a few tenants within a window,
    through padding buckets far below the tenant count's: warm them all."""
    from bench import adapter

    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(adapter, "WARM_BUCKETS", 8)
        yield
