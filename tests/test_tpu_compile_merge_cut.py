"""The device merge, compiled for a described TPU v5e (see
test_tpu_compile.py and tpu_compile_support.py): dd_top_orders' merge, cut to the top groups on the device.
One to five minutes of the compiler's time a merge, so each has a file of
its own: under `--dist loadfile` a file is one worker's work."""

import pytest

from tpu_compile_support import check_device_merge_compiles, merge_cases


@pytest.mark.parametrize("case", merge_cases("cut-16x2^20"))
def test_sparse_device_combine_compiles(one_chip, case):
    check_device_merge_compiles(one_chip, **case)
