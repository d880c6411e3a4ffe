"""The benchmark's own harness: cell lookup, data and model generation,
the float64 reference, trace reduction and result formatting.

Nothing here imports the system under test at module level; clients
import it once the chip has been found.
"""
