"""The benchmark's yardstick: cell discovery, traffic generation, weights
from the seed, the model-FLOP function, the peak table, the trace
reduction and the comparison that decides ``correct``.

Nothing here imports the program under test except ``train_cell``, which
drives it; the program never imports this package.
"""
