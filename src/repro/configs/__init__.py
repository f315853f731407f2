"""Workload configurations: ``paper_sdtw`` holds the paper's own batch
(Table 1 / Fig. 3) and the reduced shape the CPU benches use."""
