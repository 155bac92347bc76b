"""The yardstick: client, window, reference loading, comparison, trace
reduction, peaks and the bytes-needed function. Nothing here imports
from tests/, chip_smoke.py or baseline_proxy.py."""
