#!/usr/bin/env python3
"""Shim: the implementation moved to horovod_tpu/tools/hvd_trace.py so
it installs with the package (``hvd-trace`` console script).  Importing
this module yields the real one — existing ``import hvd_trace`` users
(tests) see the full surface, private names included."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from horovod_tpu.tools import hvd_trace as _impl  # noqa: E402

if __name__ == "__main__":
    sys.exit(_impl.main())
else:
    sys.modules[__name__] = _impl
