"""What every cell shares: files, statistics, the compile meter, the trace reduction, the peaks."""
