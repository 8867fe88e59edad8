"""Traffic generators: ``bench/traffic/<t>.json`` names one by its
``kind``; each turns the mix's parameters and the seed into inputs."""
