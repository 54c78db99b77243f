"""The device merge, compiled for a described TPU v5e (see
test_tpu_compile.py and tpu_compile_support.py): int64 value-space keys, as tables kept on the device send them.
One to five minutes of the compiler's time a merge, so each has a file of
its own: under `--dist loadfile` a file is one worker's work."""

import pytest

from tpu_compile_support import check_device_merge_compiles, merge_cases


@pytest.mark.parametrize("case", merge_cases("values-16x100000"))
def test_sparse_device_combine_compiles(one_chip, case):
    check_device_merge_compiles(one_chip, **case)
