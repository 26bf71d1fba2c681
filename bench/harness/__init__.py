"""The benchmark's own machinery: cell lookup, traffic, device checks,
trace reduction, work counts and the result line. Nothing here imports
the program under test; the runners under ``bench/runners`` do."""
