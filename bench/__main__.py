"""``python -m bench``: the same as ``python3 bench/run.py``."""

import sys

from bench.run import main

sys.exit(main())
