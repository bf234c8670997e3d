"""Reproduction of "A Quantitative Approach for Adopting Disaggregated
Memory in HPC Systems"."""
