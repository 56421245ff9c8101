"""End-to-end rehearsal of ``cifar10-r18feat.margin`` on the CPU, at the
cell's full pool and widths (50,000 rows of 512 features, a 512-wide
labeler); only the epochs of a retrain are cut, from 40 to 4, to keep the
CPU's share of the test short."""
from rehearsal import rehearse


def test_rehearse_margin_cell():
    rehearse("cifar10-r18feat.margin")
