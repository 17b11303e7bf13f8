"""The yardstick's own code: loading cells, building configurations,
comparisons, clocks, the trace reduction and the table of peaks.  Nothing
here imports a cell's, a configuration's or a traffic mix's name."""
