"""Traffic drivers: ``bench/traffic/<mix>.json`` names one by ``driver``."""
