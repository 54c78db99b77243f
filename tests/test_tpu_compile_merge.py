"""The device merge, compiled for a described TPU v5e (see
test_tpu_compile.py and tpu_compile_support.py): the whole merged table of dd_top_customers' shape (no cut).
One to five minutes of the compiler's time a merge, so each has a file of
its own: under `--dist loadfile` a file is one worker's work."""

import pytest

from tpu_compile_support import check_device_merge_compiles, merge_cases


@pytest.mark.parametrize("case", merge_cases("whole-16x294912"))
def test_sparse_device_combine_compiles(one_chip, case):
    check_device_merge_compiles(one_chip, **case)
