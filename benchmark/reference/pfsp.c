/* Plain reference for the PFSP cells: a recursive depth-first
 * branch-and-bound over a Taillard instance, written from the published
 * definitions and sharing no code or table with the program under test.
 *
 *   pfsp --jobs J --machines M --time_seed S --optimum C --lb lb1|lb2
 *        --ub 0|1 [--control 1]
 *
 * prints {"tree": T, "sol": S, "best": B}.
 *
 * Instance: Taillard's generator (E. Taillard, "Benchmarks for basic
 * scheduling problems", EJOR 64, 1993): a Lehmer LCG seeded per instance,
 * times in [1, 99], filled machine by machine.  Search (the conventions of
 * the Chapel reference the program follows): a node fixes a prefix of the
 * permutation; its children put each unscheduled job next.  A child that
 * completes the permutation is a leaf: counted in `sol`, never in `tree`,
 * and it improves the incumbent if its bound is below it.  Any other child
 * is counted in `tree` and explored iff its lower bound is strictly below
 * the incumbent, which starts at the published optimum C (ub 1) or at
 * infinity (ub 0).
 *
 * lb1: one-machine bound.  lb2: two-machine bound over every machine pair
 * (i < j), Johnson's rule with time lags [Lageweg, Lenstra, Rinnooy Kan
 * 1978].  `--control 1` is the control: every sum of the
 * bounds is rounded to bfloat16 (8 significant bits), so that a run of it
 * in the program's place must come out as not correct.
 */
#include <stdio.h>
#include <stdlib.h>
#include <string.h>

#define MAXN 64
#define MAXM 32
#define MAXP (MAXM * (MAXM - 1) / 2)

static int n, m, use_lb2, bf16;
static long p[MAXM][MAXN];             /* p[machine][job] */
static long min_heads[MAXM], min_tails[MAXM];
static int npairs, pair0[MAXP], pair1[MAXP];
static long lag[MAXP][MAXN];
static int johnson[MAXP][MAXN];        /* job ids in Johnson order */
static long best;
static long long tree, sol;

/* Round to the nearest bfloat16 value, ties to even (values here are >= 0). */
static long rnd(long x) {
    if (!bf16 || x < 256)
        return x;
    long step = 1;
    while ((x >> 8) >= step * 2)
        step *= 2;
    long q = x / step, r = x % step;
    if (2 * r > step || (2 * r == step && (q & 1)))
        q++;
    return q * step;
}

static long add(long a, long b) { return rnd(a + b); }
static long max2(long a, long b) { return a > b ? a : b; }

static long unif(long *seed, long low, long high) {
    const long M = 2147483647, A = 16807, B = 127773, C = 2836;
    long k = *seed / B;
    *seed = A * (*seed % B) - k * C;
    if (*seed < 0)
        *seed += M;
    double v01 = (float)*seed / (float)M; /* single-precision divide */
    return low + (long)(v01 * (high - low + 1));
}

static void tables(void) {
    for (int i = 0; i < m; i++) {
        long head = 1L << 40, tail = 1L << 40;
        for (int j = 0; j < n; j++) {
            long h = 0, t = 0;
            for (int k = 0; k < i; k++)
                h += p[k][j];
            for (int k = i + 1; k < m; k++)
                t += p[k][j];
            if (h < head)
                head = h;
            if (t < tail)
                tail = t;
        }
        min_heads[i] = head;
        min_tails[i] = tail;
    }
    npairs = 0;
    for (int a = 0; a < m - 1; a++)
        for (int b = a + 1; b < m; b++) {
            int q = npairs++;
            pair0[q] = a;
            pair1[q] = b;
            long key[MAXN];
            int part[MAXN];
            for (int j = 0; j < n; j++) {
                long l = 0;
                for (int k = a + 1; k < b; k++)
                    l += p[k][j];
                lag[q][j] = l;
                long t1 = p[a][j] + l, t2 = p[b][j] + l;
                part[j] = t1 >= t2; /* Johnson: t1 < t2 first, by t1 up;  */
                key[j] = part[j] ? -t2 : t1; /* then the rest, by t2 down */
            }
            for (int j = 0; j < n; j++) { /* insertion sort, ties by job id */
                int x = j, at = j;
                while (at > 0) {
                    int y = johnson[q][at - 1];
                    if (part[y] < part[x] || (part[y] == part[x] && key[y] <= key[x]))
                        break;
                    johnson[q][at] = y;
                    at--;
                }
                johnson[q][at] = x;
            }
        }
}

/* front: completion time of the fixed prefix on each machine after
 * appending job j to it. */
static void add_forward(long *front, int j) {
    front[0] = add(front[0], p[0][j]);
    for (int k = 1; k < m; k++)
        front[k] = add(max2(front[k - 1], front[k]), p[k][j]);
}

static long lb1(const long *front, const long *remain) {
    long t0 = add(front[0], remain[0]);
    long lb = add(t0, min_tails[0]);
    for (int k = 1; k < m; k++) {
        long t1 = max2(t0, add(front[k], remain[k]));
        lb = max2(lb, add(t1, min_tails[k]));
        t0 = t1;
    }
    return lb;
}

/* Stops at the first pair whose bound already exceeds the incumbent, as
 * the published bound library does: the child is pruned either way. */
static long lb2(const long *front, const int *fixed, long ub) {
    long lb = 0;
    for (int q = 0; q < npairs; q++) {
        int a = pair0[q], b = pair1[q];
        long t0 = front[a], t1 = front[b];
        for (int i = 0; i < n; i++) {
            int j = johnson[q][i];
            if (fixed[j])
                continue;
            t0 = add(t0, p[a][j]);
            t1 = max2(t1, add(t0, lag[q][j]));
            t1 = add(t1, p[b][j]);
        }
        lb = max2(lb, max2(add(t1, min_tails[b]), add(t0, min_tails[a])));
        if (lb > ub)
            break;
    }
    return lb;
}

static void explore(int *prmu, int depth, const long *front, const long *remain,
                    int *fixed) {
    for (int i = depth; i < n; i++) {
        int j = prmu[i];
        prmu[i] = prmu[depth];
        prmu[depth] = j;
        long cf[MAXM], cr[MAXM];
        memcpy(cf, front, sizeof cf);
        add_forward(cf, j);
        for (int k = 0; k < m; k++)
            cr[k] = remain[k] - p[k][j];
        fixed[j] = 1;
        long lb = use_lb2 ? lb2(cf, fixed, rnd(best)) : lb1(cf, cr);
        if (depth + 1 == n) {
            sol++;
            if (lb < rnd(best))
                best = lb;
        } else if (lb < rnd(best)) {
            tree++;
            explore(prmu, depth + 1, cf, cr, fixed);
        }
        fixed[j] = 0;
        prmu[depth] = prmu[i];
        prmu[i] = j;
    }
}

int main(int argc, char **argv) {
    long seed = -1, optimum = -1, ub = -1;
    n = m = 0;
    use_lb2 = -1;
    for (int a = 1; a + 1 < argc; a += 2) {
        const char *k = argv[a], *v = argv[a + 1];
        if (!strcmp(k, "--jobs"))
            n = atoi(v);
        else if (!strcmp(k, "--machines"))
            m = atoi(v);
        else if (!strcmp(k, "--time_seed"))
            seed = atol(v);
        else if (!strcmp(k, "--optimum"))
            optimum = atol(v);
        else if (!strcmp(k, "--lb"))
            use_lb2 = !strcmp(v, "lb2") ? 1 : !strcmp(v, "lb1") ? 0 : -1;
        else if (!strcmp(k, "--ub"))
            ub = atol(v);
        else if (!strcmp(k, "--control"))
            bf16 = atoi(v);
        else
            use_lb2 = -2;
    }
    if (argc % 2 != 1 || n < 2 || n > MAXN || m < 2 || m > MAXM || seed < 0 ||
        optimum < 1 || use_lb2 < 0 || (ub != 0 && ub != 1)) {
        fprintf(stderr, "usage: %s --jobs J --machines M --time_seed S --optimum C "
                        "--lb lb1|lb2 --ub 0|1 [--control 1]\n", argv[0]);
        return 2;
    }
    best = ub ? optimum : 1L << 40;
    for (int i = 0; i < m; i++)
        for (int j = 0; j < n; j++)
            p[i][j] = unif(&seed, 1, 99);
    tables();
    int prmu[MAXN], fixed[MAXN] = {0};
    long front[MAXM] = {0}, remain[MAXM] = {0};
    for (int j = 0; j < n; j++) {
        prmu[j] = j;
        for (int k = 0; k < m; k++)
            remain[k] += p[k][j];
    }
    explore(prmu, 0, front, remain, fixed);
    printf("{\"tree\": %lld, \"sol\": %lld, \"best\": %ld}\n", tree, sol, best);
    return 0;
}
