"""When this run's process started, as near as Python can tell: the
first thing ``bench.run`` does is import this module, and set-up is
timed from here."""

import time

T_START = time.perf_counter()
