"""Pre-fix shape of mxnet_tpu/test_utils.py check_speed (this PR):
elapsed-time math on the wall clock, which an NTP step bends."""
import time


def check_speed(run, N):
    tic = time.time()
    for _ in range(N):
        run()
    return (time.time() - tic) / N


def watch_deadline(hours):
    # a deadline on the wall clock moves
    # when NTP does
    return time.time() + 3600 * hours
