"""The model-zoo serving matrix for the paged route (llama, yi,
gemma3's window layers): overlapped undervolted serving == solo
``generate()`` replay, bit for bit, greedy+sampled x ECC off/on, on ONE
compiled decode step.  The state-route half and the shared helpers live
in test_zoo_serving.py.
"""
import pytest

from test_zoo_serving import PAGED_ZOO, check_zoo_matrix


@pytest.mark.parametrize("name", PAGED_ZOO)
def test_zoo_matrix(name):
    check_zoo_matrix(name)
